"""Mean milliseconds of a ``tffm:serve.coalesce`` span: first request
picked to group closed, at most ``max_batch_wait_ms`` by construction."""

import _spans


def read(run):
    return _spans.mean_ms(run, "coalesce")
