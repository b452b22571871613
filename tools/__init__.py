# Makes tools/ an importable package so `python -m tools.lint` and
# `from tools import check_tier1` work from the repo root.  The
# `sys.path.insert(0, tools); import check_tier1` spelling (tests use it)
# keeps working too — the modules have no intra-package imports.
