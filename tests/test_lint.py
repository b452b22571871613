"""tffm-lint framework tests (tools/lint — the PR 10 tentpole).

Three layers, all tier-1:

* per-rule fixture snippets: a miniature repo per analyzer where a
  seeded violation must be flagged AT THE RIGHT file:line and the
  compliant twin must pass — the analyzers are heuristic, so their
  contract is pinned by example;
* framework mechanics: baseline suppression (new vs grandfathered vs
  stale), inline ``# lint: disable=`` comments, the CLI exit code;
* the live tree: ``lint.run(repo_root)`` must report no NEW findings
  and no stale baseline entries — the same gate tools/verify.sh
  runs, so a finding introduced by any future PR fails here first.

Plus the lint-adjacent runtime gate: importing every package module
must raise no deprecation-class warning attributed to package files
(the ``-W error::DeprecationWarning``-style audit, run in a
subprocess so this process's import cache can't hide anything), and
the regression test for the leak TL005 caught on the shipped tree
(the tracer's rotation writer thread was started unbound and could
never be joined).
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import threading

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from tools import lint  # noqa: E402
from tools.lint.core import Context, load_baseline, run_rules  # noqa: E402
from tools.lint.donation import DonationRule  # noqa: E402
from tools.lint.knobs import KnobsRule  # noqa: E402
from tools.lint.legacy import ObsMetricsRule, Tier1Rule  # noqa: E402
from tools.lint.lifecycle import LifecycleRule  # noqa: E402
from tools.lint.locks import LocksRule  # noqa: E402
from tools.lint.records import RecordsRule  # noqa: E402


def _mini_repo(tmp_path, snippet: str, name="mod.py") -> Context:
    """A fixture repo holding one package module."""
    pkg = tmp_path / "fast_tffm_tpu"
    pkg.mkdir(exist_ok=True)
    (pkg / name).write_text(textwrap.dedent(snippet))
    return Context(str(tmp_path))


def _findings(rule, ctx):
    return rule.run(ctx)


def _by_rule(findings, rule_id):
    return [f for f in findings if f.rule == rule_id]


# ---------------------------------------------------------------------
# TL — lifecycle
# ---------------------------------------------------------------------

class TestLifecycle:
    def test_unjoined_attr_thread_flagged_at_line(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import threading

            class Owner:
                def __init__(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def _run(self):
                    pass
            """)
        found = _by_rule(_findings(LifecycleRule(), ctx), "TL001")
        assert len(found) == 1
        assert found[0].path == "fast_tffm_tpu/mod.py"
        assert found[0].line == 5
        assert "_t" in found[0].message

    def test_attr_thread_with_join_passes(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import threading

            class Owner:
                def __init__(self):
                    self._t = threading.Thread(target=self._run)

                def _run(self):
                    pass

                def close(self):
                    self._t.join()
            """)
        assert not _findings(LifecycleRule(), ctx)

    def test_unbound_started_thread_flagged(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import threading

            def fire():
                threading.Thread(target=print, daemon=True).start()
            """)
        found = _by_rule(_findings(LifecycleRule(), ctx), "TL005")
        assert len(found) == 1 and found[0].line == 4

    def test_container_threads_joined_pass(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import threading

            def run(n):
                threads = [threading.Thread(target=print)]
                threads += [
                    threading.Thread(target=print) for _ in range(n)
                ]
                for t in threads:
                    t.start()
                try:
                    pass
                finally:
                    for t in threads:
                        t.join()
            """)
        assert not _findings(LifecycleRule(), ctx)

    def test_container_threads_unjoined_flagged(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import threading

            def run(n):
                threads = [threading.Thread(target=print)
                           for _ in range(n)]
                for t in threads:
                    t.start()
            """)
        assert _by_rule(_findings(LifecycleRule(), ctx), "TL001")

    def test_attr_worker_pool_unjoined_flagged(self, tmp_path):
        """TL007 (ISSUE 16): the worker-pool shape — a list of threads
        bound to a self attribute, whose teardown loop would live in
        ANOTHER method.  No loop over the attribute = pooled handler
        threads that outlive their server."""
        ctx = _mini_repo(tmp_path, """\
            import threading

            class Pool:
                def __init__(self, n):
                    self._workers = [
                        threading.Thread(target=print)
                        for _ in range(n)
                    ]
                    for t in self._workers:
                        t.start()
            """)
        found = _by_rule(_findings(LifecycleRule(), ctx), "TL007")
        assert len(found) == 1
        assert "self._workers" in found[0].message

    def test_attr_worker_pool_joined_passes(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import threading

            class Pool:
                def __init__(self, n):
                    self._workers = [
                        threading.Thread(target=print)
                        for _ in range(n)
                    ]

                def close(self):
                    for t in self._workers:
                        t.join()
            """)
        assert not _findings(LifecycleRule(), ctx)

    def test_queue_shm_server_teardowns(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            from http.server import ThreadingHTTPServer
            from multiprocessing import shared_memory
            from .pipeline import _ClosableQueue

            class Owner:
                def __init__(self):
                    self._q = _ClosableQueue(4)
                    self._shm = shared_memory.SharedMemory(create=True)
                    self._httpd = ThreadingHTTPServer(("", 0), None)
            """)
        rules = {f.rule for f in _findings(LifecycleRule(), ctx)}
        assert rules == {"TL002", "TL003", "TL004"}

    def test_unreaped_popen_flagged(self, tmp_path):
        """TL006 (ISSUE 12): a subprocess.Popen replica process with
        no reachable terminate/wait on the owner's teardown path would
        outlive its router — an orphaned jax process holding a port."""
        ctx = _mini_repo(tmp_path, """\
            import subprocess

            class Manager:
                def __init__(self, cmd):
                    self.proc = subprocess.Popen(cmd)
            """)
        found = _by_rule(_findings(LifecycleRule(), ctx), "TL006")
        assert len(found) == 1
        assert found[0].line == 5
        assert "subprocess" in found[0].message

    def test_popen_with_teardown_passes(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import subprocess

            class Manager:
                def __init__(self, cmd):
                    self.proc = subprocess.Popen(cmd)

                def close(self):
                    if self.proc.poll() is None:
                        self.proc.terminate()
                    self.proc.wait()
            """)
        assert not _findings(LifecycleRule(), ctx)

    def test_local_popen_unreaped_flagged(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import subprocess

            def launch(cmd):
                proc = subprocess.Popen(cmd)
                proc.communicate()
            """)
        found = _by_rule(_findings(LifecycleRule(), ctx), "TL006")
        assert len(found) == 1

    def test_ownership_transfer_not_flagged(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            from multiprocessing import shared_memory

            class Ring:
                def __init__(self, shm):
                    self._shm = shm

                @classmethod
                def create(cls, size):
                    shm = shared_memory.SharedMemory(
                        create=True, size=size
                    )
                    return cls(shm, size)

                def close(self):
                    self._shm.close()
            """)
        assert not _findings(LifecycleRule(), ctx)


# ---------------------------------------------------------------------
# DA — donation / aliasing
# ---------------------------------------------------------------------

class TestDonation:
    def test_use_after_donate_flagged_at_line(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import jax

            step = jax.jit(lambda s, b: s, donate_argnums=0)

            def train(state, batch):
                out = step(state, batch)
                print(state)
                return out
            """)
        found = _by_rule(_findings(DonationRule(), ctx), "DA001")
        assert len(found) == 1
        assert found[0].line == 7 and "state" in found[0].message

    def test_rebind_idiom_passes(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import jax

            step = jax.jit(lambda s, b: s, donate_argnums=0)

            def train(state, batches):
                for b in batches:
                    state = step(state, b)
                return state
            """)
        assert not _findings(DonationRule(), ctx)

    def test_multiline_call_args_not_false_flagged(self, tmp_path):
        # The shipped tree's _tier_load_jit call spans lines; the
        # callee's own argument lines must not read as use-after-donate.
        ctx = _mini_repo(tmp_path, """\
            import jax

            load = jax.jit(lambda t, s, r: t, donate_argnums=0)

            def apply(tables, slots, rows):
                new_tables = load(
                    tables,
                    slots,
                    rows,
                )
                return new_tables
            """)
        assert not _findings(DonationRule(), ctx)

    def test_device_put_alias_write_flagged(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import jax
            import numpy as np

            def ship(buf, sharding):
                dev = jax.device_put(buf, sharding)
                buf[:] = 0
                return dev
            """)
        found = _by_rule(_findings(DonationRule(), ctx), "DA002")
        assert len(found) == 1 and found[0].line == 6

    def test_inline_disable_suppresses(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import jax

            def ship(buf, sharding):
                dev = jax.device_put(buf, sharding)
                buf[:] = 0  # lint: disable=DA002
                return dev
            """)
        result = run_rules([DonationRule()], ctx)
        assert not result["findings"]


# ---------------------------------------------------------------------
# LK — blocking under lock
# ---------------------------------------------------------------------

class TestLocks:
    def test_blocking_get_under_lock_flagged(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import threading

            class W:
                def __init__(self, q):
                    self._lock = threading.Lock()
                    self._q = q

                def drain(self):
                    with self._lock:
                        item = self._q.get()
                    return item
            """)
        found = _by_rule(_findings(LocksRule(), ctx), "LK001")
        assert len(found) == 1 and found[0].line == 10

    def test_timeout_and_outside_lock_pass(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import threading

            class W:
                def __init__(self, q):
                    self._lock = threading.Lock()
                    self._q = q

                def drain(self):
                    with self._lock:
                        item = self._q.get(timeout=1.0)
                    other = self._q.get()
                    return item, other
            """)
        assert not _findings(LocksRule(), ctx)

    def test_cv_wait_is_sanctioned(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import threading

            class Q:
                def __init__(self):
                    self._cv = threading.Condition()

                def get(self):
                    with self._cv:
                        while True:
                            self._cv.wait()
            """)
        assert not _findings(LocksRule(), ctx)

    def test_foreign_wait_under_lock_flagged(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import threading

            class W:
                def __init__(self, ev):
                    self._lock = threading.Lock()
                    self._ev = ev

                def hold(self):
                    with self._lock:
                        self._ev.wait()
            """)
        assert _by_rule(_findings(LocksRule(), ctx), "LK001")

    def test_nested_def_under_lock_not_flagged(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import threading

            def make(q):
                lock = threading.Lock()
                with lock:
                    def later():
                        return q.get()
                return later
            """)
        assert not _findings(LocksRule(), ctx)

    def test_dict_get_and_str_join_not_flagged(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            import threading

            def fmt(d, parts, lock):
                with lock:
                    v = d.get("key")
                    s = ", ".join(parts)
                return v, s
            """)
        assert not _findings(LocksRule(), ctx)


# ---------------------------------------------------------------------
# KD — knob drift (fixture repo with its own config/cli/docs)
# ---------------------------------------------------------------------

_KNOBS_TABLE_DRIFTED = """\
## Knobs

| knob | default | effect |
|---|---|---|
| `heartbeat_secs` (`--heartbeat_secs`) | 0 | beat |
| `phantom_knob` (`--phantom`) | 0 | drifted row |
"""

_KNOBS_TABLE_CLEAN = """\
## Knobs

| knob | default | effect |
|---|---|---|
| `heartbeat_secs` (`--heartbeat_secs`) | 0 | beat |
"""


def _knobs_repo(tmp_path, *, keymap_extra="", cli_tuple, docs,
                fingerprint="blob = dataclasses.asdict(cfg)",
                obs_table=_KNOBS_TABLE_DRIFTED):
    pkg = tmp_path / "fast_tffm_tpu"
    pkg.mkdir()
    (pkg / "config.py").write_text(textwrap.dedent(f"""\
        import dataclasses

        @dataclasses.dataclass
        class FmConfig:
            batch_size: int = 1024
            heartbeat_secs: float = 0.0
            ghost_knob: int = 0

        _KEYMAP = {{
            "batch_size": ("batch_size", int),
            "heartbeat_secs": ("heartbeat_secs", float),
            {keymap_extra}
        }}
        """))
    (pkg / "cli.py").write_text(textwrap.dedent(f"""\
        import argparse

        def build():
            p = argparse.ArgumentParser()
            p.add_argument("--heartbeat_secs", type=float)
            p.add_argument("--batch_size", type=int)
            return p

        def main(args):
            overrides = {{
                k: getattr(args, k) for k in {cli_tuple}
                if getattr(args, k) is not None
            }}
            return overrides
        """))
    (pkg / "loop.py").write_text(textwrap.dedent(f"""\
        import dataclasses

        def _config_fingerprint(cfg):
            {fingerprint}
            return str(blob)
        """))
    (tmp_path / "README.md").write_text(docs)
    (tmp_path / "OBSERVABILITY.md").write_text(obs_table)
    return Context(str(tmp_path))


class TestKnobs:
    def test_drift_matrix(self, tmp_path):
        ctx = _knobs_repo(
            tmp_path,
            keymap_extra='"typo_key": ("no_such_field", int),',
            cli_tuple='("batch_size",)',  # heartbeat flag inert
            docs="batch_size heartbeat_secs\n",  # ghost_knob undocumented
        )
        by = {}
        for f in KnobsRule().run(ctx):
            by.setdefault(f.rule, []).append(f)
        # ghost_knob: no INI key + undocumented
        assert any("ghost_knob" in f.message for f in by["KD001"])
        assert any("ghost_knob" in f.message for f in by["KD005"])
        # typo'd keymap entry
        assert any("no_such_field" in f.message for f in by["KD002"])
        # --heartbeat_secs parses but is never plumbed
        assert any("--heartbeat_secs" in f.message for f in by["KD003"])
        # docs table row for a knob that does not exist + bad CLI name
        assert any("phantom_knob" in f.message for f in by["KD006"])
        assert any("--phantom" in f.message for f in by["KD006"])

    def test_clean_fixture_passes(self, tmp_path):
        ctx = _knobs_repo(
            tmp_path,
            keymap_extra='"ghost_knob": ("ghost_knob", int),',
            cli_tuple='("batch_size", "heartbeat_secs")',
            docs="batch_size heartbeat_secs ghost_knob\n",
            obs_table=_KNOBS_TABLE_CLEAN,
        )
        findings = KnobsRule().run(ctx)
        assert not findings, [f.render() for f in findings]

    def test_fingerprint_enumeration_must_be_total(self, tmp_path):
        ctx = _knobs_repo(
            tmp_path,
            keymap_extra='"ghost_knob": ("ghost_knob", int),',
            cli_tuple='("batch_size", "heartbeat_secs")',
            docs="batch_size heartbeat_secs ghost_knob\n",
            fingerprint='blob = (cfg.batch_size, cfg.heartbeat_secs)',
            obs_table=_KNOBS_TABLE_CLEAN,
        )
        found = _by_rule(KnobsRule().run(ctx), "KD007")
        assert len(found) == 1 and "ghost_knob" in found[0].message


# ---------------------------------------------------------------------
# RS — record-schema drift (fixture repo with its own schema table)
# ---------------------------------------------------------------------

_RS_TABLE = """\
## Record schema

| record | required keys | blocks | notes |
|---|---|---|---|
| `train` | `step` `loss` | — | interval |
| `status` | — | `stages` | on demand |
| `ghost` | — | `phantom_block` | emitted nowhere |
"""

_RS_TABLE_CLEAN = """\
## Record schema

| record | required keys | blocks | notes |
|---|---|---|---|
| `train` | `step` `loss` | — | interval |
| `status` | — | `stages` | on demand |
| `ghost` | — | — | builder-called |
"""


class TestRecords:
    def _repo(self, tmp_path, snippet, table=_RS_TABLE):
        ctx = _mini_repo(tmp_path, snippet)
        (tmp_path / "OBSERVABILITY.md").write_text(
            textwrap.dedent(table)
        )
        return ctx

    def test_schema_drift_matrix(self, tmp_path):
        ctx = self._repo(tmp_path, """\
            def emit(writer):
                writer.write({
                    "record": "rogue",
                    "step": 1,
                })
                writer.write({
                    "record": "train",
                    "step": 1,
                })
            """)
        by = {}
        for f in RecordsRule().run(ctx):
            by.setdefault(f.rule, []).append(f)
        # rogue emitted but undocumented, at the dict literal's line
        assert any(
            "rogue" in f.message and f.line == 2 for f in by["RS001"]
        )
        # ghost documented but never emitted
        assert any("ghost" in f.message for f in by["RS002"])
        # the train literal lacks pinned key `loss`
        assert any("loss" in f.message for f in by["RS003"])
        # phantom_block attached nowhere
        assert any("phantom_block" in f.message for f in by["RS004"])

    def test_dynamic_builder_resolution(self, tmp_path):
        # `build(kind="status")` + `build("train")` cover both
        # documented types; `stages` attaches via subscript store.
        ctx = self._repo(tmp_path, """\
            def build(kind="status"):
                rec = {
                    "record": kind,
                    "step": 1,
                    "loss": 0.5,
                }
                rec["stages"] = {}
                return rec

            def emit():
                return build("train"), build("ghost")
            """, table=_RS_TABLE_CLEAN)
        found = RecordsRule().run(ctx)
        assert not found, [f.render() for f in found]


# ---------------------------------------------------------------------
# folded-in legacy rules
# ---------------------------------------------------------------------

class TestLegacyRules:
    def test_tier1_rule_flags_all_slow_file(self, tmp_path):
        tests = tmp_path / "tests"
        tests.mkdir()
        (tests / "test_all_slow.py").write_text(textwrap.dedent("""\
            import pytest
            pytestmark = pytest.mark.slow

            def test_one():
                pass
            """))
        (tmp_path / "pytest.ini").write_text(
            "[pytest]\nmarkers =\n    slow: slow\n"
        )
        (tmp_path / "fast_tffm_tpu").mkdir()
        found = Tier1Rule().run(Context(str(tmp_path)))
        assert len(found) == 1 and found[0].rule == "T1001"
        assert found[0].path == "tests/test_all_slow.py"

    def test_obs_metrics_rule_flags_both_directions(self, tmp_path):
        ctx = _mini_repo(tmp_path, """\
            def instrument(tel):
                return tel.counter("ingest.rogue_counter")
            """)
        (tmp_path / "OBSERVABILITY.md").write_text(textwrap.dedent("""\
            ## Metric schema

            | metric | kind | stage | meaning |
            |---|---|---|---|
            | `ingest.stale_metric` | counter | x | gone |
            """))
        by = {f.rule: f for f in ObsMetricsRule().run(ctx)}
        assert "rogue_counter" in by["OB001"].message
        assert by["OB001"].path == "fast_tffm_tpu/mod.py"
        assert "stale_metric" in by["OB002"].message


# ---------------------------------------------------------------------
# framework mechanics: baseline + CLI
# ---------------------------------------------------------------------

class TestBaseline:
    def _violating_ctx(self, tmp_path):
        return _mini_repo(tmp_path, """\
            import threading

            def fire():
                threading.Thread(target=print, daemon=True).start()
            """)

    def test_baseline_suppresses_known_finding(self, tmp_path):
        ctx = self._violating_ctx(tmp_path)
        raw = run_rules([LifecycleRule()], ctx)
        assert len(raw["new"]) == 1
        key = raw["new"][0].key
        bl = tmp_path / "baseline.txt"
        bl.write_text(f"{key}  # grandfathered: fixture\n")
        result = run_rules(
            [LifecycleRule()], ctx, load_baseline(str(bl))
        )
        assert not result["new"]
        assert len(result["baselined"]) == 1
        assert not result["stale"] and not result["uncommented"]

    def test_stale_and_uncommented_entries_reported(self, tmp_path):
        ctx = self._violating_ctx(tmp_path)
        raw = run_rules([LifecycleRule()], ctx)
        key = raw["new"][0].key
        bl = tmp_path / "baseline.txt"
        bl.write_text(
            f"{key}\n"
            "TL001:gone/file.py:Ghost.t  # fixed long ago\n"
        )
        result = run_rules(
            [LifecycleRule()], ctx, load_baseline(str(bl))
        )
        assert result["stale"] == ["TL001:gone/file.py:Ghost.t"]
        assert result["uncommented"] == [key]

    def test_baseline_key_is_line_number_free(self, tmp_path):
        ctx = self._violating_ctx(tmp_path)
        key = run_rules([LifecycleRule()], ctx)["new"][0].key
        # Shift the violation down two lines; the key must not move.
        (tmp_path / "fast_tffm_tpu" / "mod.py").write_text(
            "import threading\n\n\n\n"
            "def fire():\n"
            "    threading.Thread(target=print, daemon=True).start()\n"
        )
        ctx2 = Context(str(tmp_path))
        assert run_rules([LifecycleRule()], ctx2)["new"][0].key == key

    def test_cli_exit_codes(self, tmp_path):
        ctx = self._violating_ctx(tmp_path)
        (tmp_path / "OBSERVABILITY.md").write_text(
            _RS_TABLE.replace("| `ghost` | — | `phantom_block` | "
                              "emitted nowhere |\n", "")
        )
        env = dict(os.environ, PYTHONPATH=_REPO)
        # --no-baseline: the seeded TL005 fails the run...
        proc = subprocess.run(
            [sys.executable, "-m", "tools.lint", "--root",
             str(tmp_path), "--no-baseline", "--rules", "lifecycle"],
            capture_output=True, text=True, env=env, cwd=_REPO,
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "TL005" in proc.stdout
        # ...and a baseline carrying it exits 0.
        key = run_rules([LifecycleRule()], ctx)["new"][0].key
        bl = tmp_path / "bl.txt"
        bl.write_text(f"{key}  # fixture\n")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.lint", "--root",
             str(tmp_path), "--baseline", str(bl), "--rules",
             "lifecycle"],
            capture_output=True, text=True, env=env, cwd=_REPO,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------
# the live tree
# ---------------------------------------------------------------------

class TestLiveTree:
    def test_live_tree_clean_or_baselined(self):
        result = lint.run(root=_REPO)
        assert not result["new"], \
            "\n".join(f.render() for f in result["new"])
        assert not result["stale"], result["stale"]
        assert not result["uncommented"], result["uncommented"]

    def test_all_advertised_rules_registered(self):
        ids = set()
        for rule in lint.default_rules():
            ids.update(rule.rule_ids)
        # the five day-one analyzers + the two folded-in ancestors
        for prefix in ("TL", "DA", "LK", "KD", "RS", "T1", "OB"):
            assert any(i.startswith(prefix) for i in ids), prefix


# ---------------------------------------------------------------------
# lint-adjacent runtime gate: package imports warn-clean
# ---------------------------------------------------------------------

_IMPORT_AUDIT = """\
import os, sys, warnings, importlib

root = sys.argv[1]
mods = []
for dirpath, dirnames, files in os.walk(os.path.join(root, "fast_tffm_tpu")):
    dirnames[:] = [d for d in dirnames if d != "__pycache__"]
    for f in sorted(files):
        if f.endswith(".py"):
            rel = os.path.relpath(os.path.join(dirpath, f), root)[:-3]
            mod = rel.replace(os.sep, ".")
            mods.append(mod[:-9] if mod.endswith(".__init__") else mod)
sys.path.insert(0, root)
with warnings.catch_warnings(record=True) as caught:
    warnings.simplefilter("always")
    for m in sorted(set(mods)):
        importlib.import_module(m)
bad = [
    w for w in caught
    if issubclass(w.category, (DeprecationWarning, FutureWarning,
                               PendingDeprecationWarning))
    and ("fast_tffm_tpu" + os.sep) in (w.filename or "")
]
for w in bad:
    print(f"{w.filename}:{w.lineno}: {w.category.__name__}: {w.message}")
sys.exit(1 if bad else 0)
"""


def test_package_imports_raise_no_deprecation_warnings(tmp_path):
    """Importing every package module must trigger no deprecation-class
    warning ATTRIBUTED TO package files (third-party warnings from
    jax's own internals don't count; a deprecated jax API *we* call
    does — the warning's stacklevel lands on our line).  Subprocess:
    this process's import cache would otherwise hide everything."""
    script = tmp_path / "audit.py"
    script.write_text(_IMPORT_AUDIT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(script), _REPO],
        capture_output=True, text=True, env=env, timeout=240,
    )
    assert proc.returncode == 0, (
        "package imports raised deprecation-class warnings:\n"
        + proc.stdout + proc.stderr
    )


# ---------------------------------------------------------------------
# regression: the TL005 finding on the shipped tree (trace rotation
# writer thread was started unbound — leaked one daemon thread per
# rotating Tracer for the life of the process)
# ---------------------------------------------------------------------

class TestTracerRotateThreadLifecycle:
    def _rotating_tracer(self, tmp_path):
        from fast_tffm_tpu.obs.trace import Tracer

        return Tracer(
            enabled=True, rotate_events=10,
            rotate_path=str(tmp_path / "trace.json"),
        )

    def test_close_joins_writer_thread(self, tmp_path):
        tracer = self._rotating_tracer(tmp_path)
        assert any(
            th.name == "trace-rotate" for th in threading.enumerate()
        )
        for i in range(25):  # cross the watermark twice
            tracer.emit("ev", 0.0, 0.001, args={"i": i})
        tracer.dump(str(tmp_path / "trace.json"))
        tracer.close()
        assert not any(
            th.name == "trace-rotate" and th.is_alive()
            for th in threading.enumerate()
        )
        # every rotated window landed before close returned
        wins = sorted(p.name for p in tmp_path.glob("trace.*.json"))
        assert len(wins) >= 2

    def test_close_is_idempotent_and_safe_after(self, tmp_path):
        tracer = self._rotating_tracer(tmp_path)
        tracer.close()
        tracer.close()
        # post-close emits fall back to the capped buffer, never hang
        tracer.emit("late", 0.0, 0.001)
        out = tmp_path / "late.json"
        tracer.dump(str(out))
        assert out.exists()

    def test_null_tracer_close_is_noop(self):
        from fast_tffm_tpu.obs.trace import NULL_TRACER

        NULL_TRACER.close()  # must not raise (no rotation machinery)

    def test_reset_rearms_rotation_after_close(self, tmp_path):
        """A warm owner's second run must rotate exactly like the
        first: close() stops run 1's writer thread, reset() re-arms
        (review finding — rotation used to die permanently)."""
        tracer = self._rotating_tracer(tmp_path)
        for i in range(15):
            tracer.emit("ev", 0.0, 0.001, args={"i": i})
        tracer.dump(str(tmp_path / "trace.json"))
        tracer.close()
        run1 = set(p.name for p in tmp_path.glob("trace.*.json"))
        assert run1
        tracer.reset()  # run 2 begins
        assert any(
            th.name == "trace-rotate" and th.is_alive()
            for th in threading.enumerate()
        )
        for i in range(15):
            tracer.emit("ev2", 0.0, 0.001, args={"i": i})
        tracer.dump(str(tmp_path / "trace.json"))
        tracer.close()
        run2 = set(p.name for p in tmp_path.glob("trace.*.json"))
        # run 2 rewrote the same window family from index 0
        assert run2 >= run1 and "trace.0.json" in run2


def test_cli_rules_subset_ignores_other_rules_baseline(tmp_path):
    """`--rules locks` must not report a TL baseline entry as stale
    (review finding: a subset run can't see other rules' findings, so
    their baseline entries are invisible, not fixed)."""
    pkg = tmp_path / "fast_tffm_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text("x = 1\n")
    bl = tmp_path / "bl.txt"
    bl.write_text("TL001:fast_tffm_tpu/gone.py:Ghost.t  # debt\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--root", str(tmp_path),
         "--baseline", str(bl), "--rules", "locks"],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=_REPO), cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "stale baseline entry" not in proc.stdout
