"""RS00x — JSONL record-schema drift analyzer.

check_obs pinned metric NAMES; this rule generalizes the discipline to
the full ``record:`` catalogue of the metrics stream (run_header /
train / validation / heartbeat / final / compile / alert / status, plus
the ``health`` / ``tiered`` / ``resource`` / ``serve`` / ``stages``
blocks that ride the heartbeat-shaped records), pinned against the
"## Record schema" table in OBSERVABILITY.md.  The failure mode is the
same on both sides: a record type code emits but the docs never name
is invisible to everyone parsing the stream from the docs
(tools/report.py included); a documented type nothing emits is a
dashboard watching a stream that will never carry it.

Code-side collection is static and covers the repo's two idioms:

- literal sites: any dict literal with a ``"record": "<type>"`` entry;
- builder sites: a function whose record dict reads the type from a
  parameter (``def build(kind="status"): {... "record": kind ...}``) —
  the analyzer resolves every string literal passed to that function
  (plus the parameter default) into emitted types.

Checks:

- RS001  a record type emitted in code but absent from the table;
- RS002  a documented record type nothing emits (stale row);
- RS003  a LITERAL record dict missing keys the table pins as required
         for its type (dynamic builders can't be key-checked
         statically and are exempt);
- RS004  a documented block name never attached to any record in code.
"""

from __future__ import annotations

import ast
import re

from tools.lint.core import Context, Finding

_BACKTICK = re.compile(r"`([^`]+)`")


def parse_schema_table(md_text: str):
    """Rows of the ``## Record schema`` table.

    Expected columns: ``| record | required keys | blocks | notes |``.
    Returns ({record: (required_keys, lineno)}, {block: lineno})."""
    records: dict = {}
    blocks: dict = {}
    in_section = False
    for lineno, line in enumerate(md_text.splitlines(), 1):
        stripped = line.strip()
        if stripped.startswith("## "):
            in_section = stripped.startswith("## Record schema")
            continue
        if not in_section or not stripped.startswith("|"):
            continue
        cells = [c.strip() for c in stripped.strip("|").split("|")]
        if len(cells) < 3 or set(cells[0]) <= {"-", " ", ":"}:
            continue
        names = _BACKTICK.findall(cells[0])
        if not names or names[0] == "record":
            continue
        required = tuple(_BACKTICK.findall(cells[1]))
        records[names[0]] = (required, lineno)
        for b in _BACKTICK.findall(cells[2]):
            if b != "—":
                blocks.setdefault(b, lineno)
    return records, blocks


def _collect_emissions(ctx: Context):
    """Scan the package for emitted record types.

    Returns (literal_sites, dynamic_types, attached_keys) where
    ``literal_sites`` is [(type, rel, line, literal_keys)],
    ``dynamic_types`` is {type: (rel, line)} resolved through builder
    parameters, and ``attached_keys`` is every string constant used as
    a dict-literal key or subscript-store key anywhere in the package
    (the block-attachment surface)."""
    literal_sites = []
    attached = {}

    for rel in ctx.package_files():
        tree = ctx.tree(rel)
        if tree is None:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Dict):
                keys = [
                    k.value for k in node.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)
                ]
                for k in keys:
                    attached.setdefault(k, (rel, node.lineno))
                for k, v in zip(node.keys, node.values):
                    if not (
                        isinstance(k, ast.Constant)
                        and k.value == "record"
                    ):
                        continue
                    if isinstance(v, ast.Constant) and isinstance(
                        v.value, str
                    ):
                        literal_sites.append(
                            (v.value, rel, node.lineno, set(keys))
                        )
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if (
                        isinstance(tgt, ast.Subscript)
                        and isinstance(tgt.slice, ast.Constant)
                        and isinstance(tgt.slice.value, str)
                    ):
                        attached.setdefault(
                            tgt.slice.value, (rel, tgt.lineno)
                        )

    # Resolve dynamic builders: find the function whose parameter feeds
    # the "record" value, then every literal argument at its call
    # sites (any file) plus the parameter default.
    dynamic: dict = {}
    builder_fns = []  # (rel, func name, param name, param index, default)
    for rel in ctx.package_files():
        tree = ctx.tree(rel)
        if tree is None:
            continue
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            # Does this function build a {"record": <param>} dict?
            params = [a.arg for a in fn.args.args]
            dict_names = set()
            for node in ast.walk(fn):
                if isinstance(node, ast.Dict):
                    for k, v in zip(node.keys, node.values):
                        if (
                            isinstance(k, ast.Constant)
                            and k.value == "record"
                            and isinstance(v, ast.Name)
                        ):
                            dict_names.add(v.id)
            for pname in dict_names:
                if pname not in params:
                    continue
                idx = params.index(pname)
                default = None
                n_defaults = len(fn.args.defaults)
                if n_defaults and idx >= len(params) - n_defaults:
                    d = fn.args.defaults[idx - (len(params) - n_defaults)]
                    if isinstance(d, ast.Constant) and isinstance(
                        d.value, str
                    ):
                        default = d.value
                builder_fns.append((rel, fn.name, pname, idx, default))

    for rel, fname, pname, idx, default in builder_fns:
        if default:
            dynamic.setdefault(default, (rel, 1))
        for rel2 in ctx.package_files():
            tree = ctx.tree(rel2)
            if tree is None:
                continue
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                tname = (
                    func.attr if isinstance(func, ast.Attribute)
                    else func.id if isinstance(func, ast.Name) else ""
                )
                if tname != fname:
                    continue
                # positional (account for a leading self at methods
                # called as attributes) or keyword
                cands = []
                for off in (0, -1):  # plain call / bound-method call
                    pos = idx + off
                    if 0 <= pos < len(node.args):
                        cands.append(node.args[pos])
                for kw in node.keywords:
                    if kw.arg == pname:
                        cands.append(kw.value)
                for c in cands:
                    if isinstance(c, ast.Constant) and isinstance(
                        c.value, str
                    ):
                        dynamic.setdefault(
                            c.value, (rel2, node.lineno)
                        )
    return literal_sites, dynamic, attached


class RecordsRule:
    name = "records"
    rule_ids = ("RS001", "RS002", "RS003", "RS004")

    def run(self, ctx: Context):
        findings = []
        if not ctx.exists(ctx.obs_md):
            return findings
        documented, doc_blocks = parse_schema_table(ctx.source(ctx.obs_md))
        literal_sites, dynamic, attached = _collect_emissions(ctx)

        emitted: dict = {}
        for rtype, rel, line, _keys in literal_sites:
            emitted.setdefault(rtype, (rel, line))
        for rtype, site in dynamic.items():
            emitted.setdefault(rtype, site)

        if not documented:
            findings.append(Finding(
                rule="RS002", path=ctx.obs_md, line=1,
                message="no '## Record schema' table found — the "
                        "record catalogue is unpinned",
                hint="add the table (see LINTING.md)",
                symbol="<missing-table>",
            ))
            return findings

        for rtype, (rel, line) in sorted(emitted.items()):
            if rtype not in documented:
                findings.append(Finding(
                    rule="RS001", path=rel, line=line,
                    message=f"record type `{rtype}` is emitted here "
                            "but absent from OBSERVABILITY.md's "
                            "Record schema table",
                    hint="add a row documenting the record",
                    symbol=rtype,
                ))
        for rtype, (_req, line) in sorted(documented.items()):
            if rtype not in emitted:
                findings.append(Finding(
                    rule="RS002", path=ctx.obs_md, line=line,
                    message=f"documented record type `{rtype}` is "
                            "emitted nowhere in the package",
                    hint="remove the row or fix the emitting code",
                    symbol=rtype,
                ))
        for rtype, rel, line, keys in literal_sites:
            req, _ = documented.get(rtype, ((), 0))
            missing = [k for k in req if k not in keys]
            if missing:
                findings.append(Finding(
                    rule="RS003", path=rel, line=line,
                    message=(
                        f"literal `{rtype}` record is missing pinned "
                        f"key(s) {missing}"
                    ),
                    hint="emit the keys or update the Record schema "
                         "table",
                    symbol=f"{rtype}@{rel}",
                ))
        for block, line in sorted(doc_blocks.items()):
            if block not in attached:
                findings.append(Finding(
                    rule="RS004", path=ctx.obs_md, line=line,
                    message=f"documented block `{block}` is never "
                            "attached to any record in code",
                    hint="remove it from the table or fix the "
                         "attaching code",
                    symbol=block,
                ))
        return findings
