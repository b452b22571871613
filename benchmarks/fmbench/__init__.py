"""The yardstick: everything here is the benchmark's own and imports
nothing of ``fast_tffm_tpu`` (the drivers do; these modules never)."""
