"""chip_smoke.py rehearsed in-process, and the bring-up guards it rests on.

The smoke's phases run here at toy size on the CPU backend (Pallas in
interpret mode) — wrong paths, arguments and control flow cost no chip
time.  The rest pins what keeps the chip run honest: without a TPU the
full-size invocation refuses, the compile cache goes where the
environment says, the C++ parser is never quietly replaced by the Python
oracle (nor by a stale binary), and an autotune candidate that fails to
run is an error, not a loser.
"""

from __future__ import annotations

import ctypes
import json
import os
import shutil

import jax
import numpy as np
import pytest

import chip_smoke
from fast_tffm_tpu import platform
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data import native, pipeline
from fast_tffm_tpu.ops import autotune
from fast_tffm_tpu.parallel import mesh as mesh_lib


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR -> a tmp dir; cache state restored."""
    d = str(tmp_path / "env_cc")
    monkeypatch.setenv(platform.ENV_COMPILE_CACHE, d)
    yield d
    platform.disable_compile_cache()


# ----------------------------------------------------------- the smoke


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_runs_every_phase_in_process(
    chips, tmp_path, monkeypatch, capsys, cache_env
):
    monkeypatch.setenv("FAST_TFFM_FUSED_H2D", "1")
    monkeypatch.setenv("FAST_TFFM_AUTOTUNE_CACHE", "")
    if chips == 1:
        # conftest gives this process 8 virtual devices, and make_mesh
        # turns a 1x1 cfg with several devices into pure data-parallel;
        # the one-chip machine has one device.  Steer it here, in the
        # test (the 2x2 cfg of --chips 4 takes devices[:4] by itself).
        real = mesh_lib.make_mesh
        monkeypatch.setattr(
            mesh_lib, "make_mesh",
            lambda cfg, devices=None: real(
                cfg, devices if devices is not None else jax.devices()[:1]
            ),
        )
    final = chip_smoke.run(
        chips=chips, rehearse=True, work=str(tmp_path / "work")
    )
    assert final == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": chips},
    }
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert lines[-1] == final  # the result is the LAST line
    phases = [x["phase"] for x in lines[:-1]]
    want = (["device", "inputs", "sharded", "compile_cache"] if chips == 4
            else ["device", "inputs", "train", "kernels", "ffm", "predict",
                  "serve", "compile_cache"])
    assert phases == want
    assert lines[-2]["dir"] == cache_env  # the environment placed the cache
    assert not os.path.exists(tmp_path / "work")  # cleaned up


def test_full_size_refuses_without_a_tpu(tmp_path, capsys):
    """No accelerator: nonzero exit, nothing on stdout, no CPU fallback."""
    with pytest.raises(SystemExit) as exc:
        chip_smoke.run(chips=1, rehearse=False, work=str(tmp_path / "w"))
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_full_size_cfg_is_criteo_kaggle_but_for_paths(tmp_path):
    """FULL changes no width: the cfg the chip run trains from is
    examples/criteo_kaggle.cfg with other file paths (make_inputs
    asserts the field-by-field comparison itself)."""
    assert chip_smoke.FULL.overrides == {}
    tiny = chip_smoke.Size({}, 1, 1, 1, 1)
    _, cfg, _ = chip_smoke.make_inputs(str(tmp_path), tiny, seed=0)
    assert (cfg.vocabulary_size, cfg.factor_num, cfg.max_features,
            cfg.batch_size, cfg.steps_per_dispatch) == (1 << 22, 8, 39,
                                                        4096, 1)
    with open(cfg.train_files[0]) as f:
        first = f.readline().split()
    assert len(first) == 1 + 39  # label + 39 id:val features


# --------------------------------------------------- compile-cache placement


@pytest.mark.parametrize("knob", ["", "cfg_dir"])
def test_compile_cache_env_wins_over_cfg_knob(knob, tmp_path, cache_env):
    knob = str(tmp_path / knob) if knob else ""
    assert platform.compile_cache_dir(knob) == cache_env
    assert platform.enable_compile_cache(knob)
    assert jax.config.jax_compilation_cache_dir == cache_env
    assert platform.compile_cache_stats()["dir"] == cache_env
    assert not (knob and os.path.exists(knob))
    # The autotune cache follows the same directory.
    cfg = FmConfig(vocabulary_size=64, factor_num=2,
                   compile_cache_dir=knob, model_file=str(tmp_path / "m"))
    assert autotune.default_cache_path(cfg) == os.path.join(
        cache_env, "autotune_cache.json"
    )


def test_compile_cache_unset_env_uses_knob_or_fixed_repo_path(
    tmp_path, monkeypatch
):
    monkeypatch.delenv(platform.ENV_COMPILE_CACHE, raising=False)
    assert platform.enable_compile_cache("") is False  # neither: no cache
    knob = str(tmp_path / "cfg_cc")
    try:
        assert platform.enable_compile_cache(knob)
        assert jax.config.jax_compilation_cache_dir == knob
    finally:
        platform.disable_compile_cache()
    # The scripts' default: one fixed path in the checkout, the same in
    # every process — no temp name, pid or time in it.
    repo = os.path.dirname(os.path.abspath(chip_smoke.__file__))
    assert platform.REPO_COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    src = open(os.path.join(repo, "chip_smoke.py")).read()
    assert "enable_compile_cache(" in src
    assert "REPO_COMPILE_CACHE_DIR" in src


# ------------------------------------------------------------ native parser


def _parser_cfg():
    return FmConfig(vocabulary_size=64, factor_num=2, max_features=4,
                    batch_size=2)


def test_make_parser_raises_when_the_library_cannot_load(monkeypatch):
    def boom():
        raise OSError("libfm_parser: cannot open shared object file")

    monkeypatch.setattr(native, "_load", boom)
    with pytest.raises(OSError, match="libfm_parser"):
        pipeline._make_parser(_parser_cfg())


def test_stale_parser_binary_next_to_changed_source_is_not_loaded(
    tmp_path, monkeypatch
):
    """The artifact is keyed on the source's CONTENT: after an edit the
    old binary (whatever its mtime) has another name and a fresh one is
    built from the source at hand."""
    src_dir = tmp_path / "_src"
    src_dir.mkdir()
    shutil.copy(native._SRC, src_dir / "fm_parser.cc")
    monkeypatch.setattr(native, "_SRC_DIR", str(src_dir))
    monkeypatch.setattr(native, "_SRC", str(src_dir / "fm_parser.cc"))
    old = native._lib_path()
    # Stale/foreign binaries: the pre-PR fixed name and the old key,
    # both unloadable garbage with mtimes NEWER than the source.
    for stale in (src_dir / "libfm_parser.so", old):
        with open(stale, "wb") as f:
            f.write(b"not an ELF file")
    with open(src_dir / "fm_parser.cc", "a") as f:
        f.write("\n// edited\n")
    new = native._lib_path()
    assert new != old and os.path.dirname(new) == str(src_dir)
    assert native._build() == new
    lib = ctypes.CDLL(new)  # a real library, built from the edited source
    assert hasattr(lib, "fm_parser_create")
    assert open(old, "rb").read() == b"not an ELF file"  # never touched


# ----------------------------------------------------------------- autotune


def test_autotune_candidate_that_fails_to_run_raises(monkeypatch):
    """On the chip a Pallas kernel the compiler refuses must not quietly
    give way to the reference: a candidate that fails to run raises."""
    monkeypatch.setattr(platform, "is_tpu_backend", lambda: True)
    monkeypatch.setenv("FAST_TFFM_AUTOTUNE_CACHE", "")
    cfg = FmConfig(vocabulary_size=64, factor_num=4, max_features=6,
                   batch_size=32, interaction_impl="auto")
    assert autotune.default_candidates(0) == ("reference", "pallas",
                                              "packed")
    rows = np.zeros((32, 6, 4), np.float32)
    vals = np.ones((32, 6), np.float32)

    def make(user_impl):
        from fast_tffm_tpu.ops import interaction

        def f(r, v):
            if user_impl == "pallas":
                raise RuntimeError("Mosaic failed to compile TPU kernel")
            return interaction.fm_interaction(r, v, "jnp")

        return f

    with pytest.raises(RuntimeError, match="Mosaic failed"):
        autotune.resolve(
            cfg, candidates=("reference", "pallas"),
            candidate_fns=(make, (rows, vals)), jax_version="fails-to-run",
        )


# ------------------------------------------------------- one process per chip


def test_replica_env_gives_replica_i_chip_i_and_refuses_too_many():
    from fast_tffm_tpu.serve import router

    base = {"PATH": "/bin"}
    assert router._replica_env(base, 1, 2, chips=0) == base  # CPU host
    envs = [router._replica_env(base, i, 2, chips=4) for i in range(2)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               and e["PATH"] == "/bin" for e in envs)
    assert base == {"PATH": "/bin"}  # the parent's env is not edited
    with pytest.raises(ValueError, match="4 TPU chip"):
        router._replica_env(base, 0, 5, chips=4)


def test_router_counts_no_chips_when_the_env_pins_cpu(monkeypatch):
    from fast_tffm_tpu.serve import router

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert router._host_tpu_chips() == 0
