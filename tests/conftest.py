"""Test environment: force an 8-device virtual CPU mesh.

This is the TPU-world analogue of the reference's "localhost PS cluster"
smoke tests (SURVEY.md §4): multi-chip sharding paths run on 8 fake CPU
devices so the full mesh logic is exercised without TPU hardware.

Tests stay CPU-only whatever the environment offers: the XLA flag is set
before jax initializes its CPU client, and the platform list is forced to
"cpu" through jax.config.
"""

from fast_tffm_tpu.platform import pin_cpu

# Must happen before jax initializes its CPU client.
pin_cpu(8)

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def set_data_state(model_file: str, **fields) -> None:
    """Rewrite checkpointed input-pipeline position fields, preserving the
    saved stream fingerprint — the shared way tests simulate a mid-epoch
    interruption (tests that deliberately write a raw/fingerprint-less
    data_state.json to exercise back-compat keep doing so inline)."""
    import json

    from fast_tffm_tpu.train import checkpoint

    ds = checkpoint.restore_data_state(model_file)
    assert ds is not None, f"no data_state in {model_file}"
    ds.update(fields)
    with open(f"{model_file}/data_state.json", "w") as f:
        json.dump(ds, f)
