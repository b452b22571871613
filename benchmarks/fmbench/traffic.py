"""One general generator of serving traffic, driven by a data file.

A mix (``traffic/<name>.json``) gives the loop (open: Poisson arrivals at
a rate fixed in the file, steady or in on/off bursts; closed: N clients
back to back), the number of keep-alive connections, how many examples a
request holds, and the share of text requests.  Every seed gets the SAME set of request sizes, kinds
and arrival gaps (drawn from the file's ``shape_seed``), in another
order, and its own ids and values: the seed moves the content, never the
amount of work.

numpy only: the load generator's process imports this and never jax.
"""

from __future__ import annotations

import struct

import numpy as np

from fmbench import synth

BIN_MAGIC = b"TFB1"  # SERVING.md "Binary frame layout", little-endian
_BIN_HDR = struct.Struct("<4sIIB")
_BIN_RESP_HDR = struct.Struct("<4sI")


def _sizes(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] == "const":
        return np.full((n,), int(spec["n"]), np.int64)
    if spec["dist"] == "lognormal":
        x = np.ceil(rng.lognormal(np.log(spec["median"]), spec["sigma"], n))
        return np.clip(x, 1, spec["max"]).astype(np.int64)
    raise ValueError(f"unknown size distribution {spec['dist']!r}")


def burst_clock(bursts: dict, seconds: float) -> tuple:
    """``(mass, times, masses)`` of an open loop whose rate is the file's
    ``rate_per_s`` times ``on_factor`` for the first ``on_s`` of every
    ``period_s`` and times ``off_factor`` for the rest: the integral of
    that multiplier over the window, and the piecewise-linear map from a
    point of it back to the window's clock."""
    period, on = float(bursts["period_s"]), float(bursts["on_s"])
    if not 0 < on < period:
        raise ValueError("bursts need 0 < on_s < period_s")
    times, masses = [0.0], [0.0]
    while times[-1] < seconds:
        into = times[-1] % period
        is_on = into < on - 1e-12
        nxt = min(seconds, times[-1] - into + (on if is_on else period))
        factor = bursts["on_factor"] if is_on else bursts["off_factor"]
        masses.append(masses[-1]
                      + max(float(factor), 1e-9) * (nxt - times[-1]))
        times.append(nxt)
    return masses[-1], np.asarray(times), np.asarray(masses)


def make_plan(mix: dict, *, seconds: float, seed: int, vocab: int,
              features: int, rate: float = 0.0) -> dict:
    """The requests of one run.  Open loop: one entry per arrival in the
    window, with its due time.  Closed loop: a pool of bodies that the
    clients cycle through."""
    shape = np.random.default_rng(mix["shape_seed"])
    rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        rate = rate or mix["rate_per_s"]
        mass, clock = seconds, None
        if mix.get("bursts"):
            mass, *clock = burst_clock(mix["bursts"], seconds)
        n = max(1, int(round(rate * mass)))
        gaps = shape.exponential(1.0, n)
        gaps *= mass / gaps.sum()  # the same span for every seed
    elif mix["loop"] == "closed":
        n = int(mix["pool"])
        gaps = None
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    sizes = _sizes(mix["examples"], n, shape)
    text = np.zeros((n,), bool)
    text[:int(round(mix.get("text_share", 0.0) * n))] = True
    order = rng.permutation(n)
    sizes, text = sizes[order], text[order]
    plan = {"loop": mix["loop"], "n": sizes, "text": text,
            "start": np.concatenate([[0], np.cumsum(sizes)]),
            "vocab": vocab, "features": features}
    if gaps is not None:
        gaps = gaps[rng.permutation(n)]
        plan["gaps"] = gaps
        plan["due"] = np.cumsum(gaps) - gaps[0]
        if clock is not None:  # from the multiplier's integral to seconds
            plan["due"] = np.interp(plan["due"], clock[1], clock[0])
    total = int(sizes.sum())
    plan["raw_ids"] = synth.zipf_ids(rng, (total, features), vocab)
    plan["v4"] = synth.val4(rng, (total, features)).astype(np.int32)
    return plan


def request_arrays(plan: dict, i: int) -> tuple:
    lo, hi = plan["start"][i], plan["start"][i + 1]
    return plan["raw_ids"][lo:hi], plan["v4"][lo:hi]


def values(v4: np.ndarray) -> np.ndarray:
    """The float32 nearest the four decimals written."""
    return (v4 / 1e4).astype(np.float32)


def encode_bin(raw_ids: np.ndarray, v4: np.ndarray) -> bytes:
    n, f = raw_ids.shape
    return (_BIN_HDR.pack(BIN_MAGIC, n, f, 0)
            + np.ascontiguousarray(raw_ids, "<i4").tobytes()
            + np.ascontiguousarray(values(v4), "<f4").tobytes())


def decode_bin_response(data: bytes) -> np.ndarray:
    magic, n = _BIN_RESP_HDR.unpack_from(data)
    if magic != BIN_MAGIC or len(data) != _BIN_RESP_HDR.size + 4 * n:
        raise ValueError("malformed binary reply")
    return np.frombuffer(data, "<f4", n, _BIN_RESP_HDR.size)


def encode_bodies(plan: dict) -> list:
    """(path, body) per request.  Text lines are ``0 id:0.dddd ...``."""
    n = len(plan["n"])
    out = [None] * n
    t_idx = np.flatnonzero(plan["text"])
    if len(t_idx):
        rows = np.concatenate([np.arange(plan["start"][i],
                                         plan["start"][i + 1])
                               for i in t_idx])
        lines = synth.libsvm_lines(None, plan["raw_ids"][rows],
                                   plan["v4"][rows])
        pos = 0
        for i in t_idx:
            k = int(plan["n"][i])
            out[i] = ("/score", b"\n".join(lines[pos:pos + k]) + b"\n")
            pos += k
    for i in np.flatnonzero(~plan["text"]):
        out[i] = ("/score_bin", encode_bin(*request_arrays(plan, i)))
    return out


def decode_reply(path: str, body: bytes) -> np.ndarray:
    if path == "/score_bin":
        return decode_bin_response(body)
    return np.array(body.split(), np.float64).astype(np.float32)
