"""The load generator: its own process (it never imports jax and never
touches the chip), few threads, raw keep-alive sockets.

Protocol with the parent over stdin/stdout, one line each way:

    child:  GENERATED            requests built from the seed
    parent: PORT <n>             the server is up
    child:  WARM                 every path and rung has been sent once
    parent: GO                   the window opens now
    child:  DONE <json>          the window closed; results are in --out

Open loop: request i is due at ``t0 + due[i]``; a free connection takes
the next due request, waits for its time, sends it and reads the reply.
Latency counts from when the request was DUE, so a stall is charged to
every request it delays; how late the generator itself ran is reported.
Closed loop: each client sends the pool's bodies back to back until the
window's seconds are over; a reply counts if it arrived inside them.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from fmbench import traffic  # noqa: E402


class Conn:
    def __init__(self, port: int, timeout: float):
        self.port, self.timeout = port, timeout
        self.sock = None
        self.buf = b""

    def _open(self):
        self.sock = socket.create_connection(("127.0.0.1", self.port),
                                             timeout=self.timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""

    def close(self):
        if self.sock is not None:
            try:
                self.sock.close()
            finally:
                self.sock = None

    def post(self, path: str, body: bytes) -> tuple:
        """(status, reply body).  Status 0 = no answer (timeout, reset)."""
        head = (f"POST {path} HTTP/1.1\r\nHost: bench\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode()
        try:
            if self.sock is None:
                self._open()
            self.sock.sendall(head + body)
            while b"\r\n\r\n" not in self.buf:
                chunk = self.sock.recv(65536)
                if not chunk:
                    raise ConnectionError("closed")
                self.buf += chunk
            hdr, rest = self.buf.split(b"\r\n\r\n", 1)
            lines = hdr.split(b"\r\n")
            status = int(lines[0].split()[1])
            length, close = 0, False
            for ln in lines[1:]:
                k, _, v = ln.partition(b":")
                k = k.strip().lower()
                if k == b"content-length":
                    length = int(v)
                elif k == b"connection" and v.strip().lower() == b"close":
                    close = True
            while len(rest) < length:
                chunk = self.sock.recv(max(65536, length - len(rest)))
                if not chunk:
                    raise ConnectionError("closed")
                rest += chunk
            reply, self.buf = rest[:length], rest[length:]
            if close:
                self.close()
            return status, reply
        except (OSError, ValueError, IndexError):
            self.close()
            return 0, b""


def warm_up(port: int, plan: dict, bodies: list, timeout: float) -> None:
    """Once through each path at the smallest and the largest request:
    connections, parse scratch and rung staging are then warm (the rungs
    themselves were compiled by the server's own warm-up)."""
    conn = Conn(port, timeout)
    for is_text in (False, True):
        idx = np.flatnonzero(plan["text"] == is_text)
        if not len(idx):
            continue
        order = idx[np.argsort(plan["n"][idx], kind="stable")]
        for i in {int(order[0]), int(order[len(order) // 2]),
                  int(order[-1])}:
            status, _ = conn.post(*bodies[i])
            if status != 200:
                raise RuntimeError(f"warm-up request got status {status}")
    conn.close()


def run_open(port, plan, bodies, mix, seconds):
    n = len(bodies)
    due = plan["due"]
    status = np.zeros((n,), np.int32)
    latency = np.full((n,), np.nan)
    late = np.zeros((n,))
    replies = [None] * n
    nxt = [0]
    lock = threading.Lock()
    timeout = float(mix.get("reply_timeout_s", 60))
    t0 = time.perf_counter() + 0.05

    def worker():
        conn = Conn(port, timeout)
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            if i >= n:
                break
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent = time.perf_counter()
            st, reply = conn.post(*bodies[i])
            done = time.perf_counter()
            status[i] = st
            late[i] = sent - (t0 + due[i])
            latency[i] = done - (t0 + due[i])
            replies[i] = reply
        conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(mix["connections"]))]
    wall0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"status": status, "latency": latency, "late": late,
            "replies": replies, "wall0": wall0 + 0.05,
            "span_s": time.perf_counter() - t0}


def run_closed(port, plan, bodies, mix, seconds):
    pool = len(bodies)
    clients = int(mix["clients"])
    timeout = float(mix.get("reply_timeout_s", 60))
    first = [None] * pool  # first reply per body, kept for the check
    repeat_gap = [0.0] * clients
    counts = [dict(ok=0, failed=0, examples=0, attempted=0)
              for _ in range(clients)]
    lat = [[] for _ in range(clients)]
    lock = threading.Lock()
    t0 = time.perf_counter() + 0.05
    t_end = t0 + seconds

    def worker(c):
        conn = Conn(port, timeout)
        i = (c * pool) // clients
        time.sleep(max(0.0, t0 - time.perf_counter()))
        while True:
            sent = time.perf_counter()
            if sent >= t_end:
                break
            path, body = bodies[i]
            st, reply = conn.post(path, body)
            done = time.perf_counter()
            if done > t_end:
                break  # arrived after the window: not counted
            counts[c]["attempted"] += 1
            if st != 200:
                counts[c]["failed"] += 1
            else:
                got = traffic.decode_reply(path, reply)
                with lock:
                    if first[i] is None:
                        first[i] = got
                        ref = got
                    else:
                        ref = first[i]
                if got.shape != ref.shape:
                    counts[c]["failed"] += 1
                else:
                    repeat_gap[c] = max(repeat_gap[c], float(
                        np.abs(got - ref).max()))
                    counts[c]["ok"] += 1
                    counts[c]["examples"] += len(got)
                    lat[c].append(done - sent)
            i = (i + 1) % pool
        conn.close()

    threads = [threading.Thread(target=worker, args=(c,), daemon=True)
               for c in range(clients)]
    wall0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"first": first, "repeat_gap": max(repeat_gap),
            "counts": counts, "latency": np.concatenate(
                [np.asarray(x, float) for x in lat]) if any(lat)
            else np.zeros((0,)), "wall0": wall0 + 0.05,
            "span_s": seconds}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True)
    args = ap.parse_args()
    with open(args.spec) as f:
        spec = json.load(f)
    mix = spec["mix"]
    plan = traffic.make_plan(
        mix, seconds=spec["seconds"], seed=spec["seed"],
        vocab=spec["vocab"], features=spec["features"],
        rate=spec.get("rate", 0.0))
    bodies = traffic.encode_bodies(plan)
    print("GENERATED", flush=True)
    port = int(sys.stdin.readline().split()[1])
    warm_up(port, plan, bodies, float(mix.get("reply_timeout_s", 60)))
    print("WARM", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 1
    if mix["loop"] == "open":
        res = run_open(port, plan, bodies, mix, spec["seconds"])
        ok = res["status"] == 200
        scores, offs = [], [0]
        for i, reply in enumerate(res["replies"]):
            got = np.zeros((0,), np.float32)
            if ok[i]:
                try:
                    got = traffic.decode_reply(bodies[i][0], reply)
                except ValueError:
                    res["status"][i] = -1
            scores.append(got)
            offs.append(offs[-1] + len(got))
        np.savez(spec["out"], status=res["status"], latency=res["latency"],
                 late=res["late"], scores=np.concatenate(scores),
                 offsets=np.asarray(offs), wall0=res["wall0"],
                 span_s=res["span_s"])
    else:
        res = run_closed(port, plan, bodies, mix, spec["seconds"])
        have = [i for i, x in enumerate(res["first"]) if x is not None]
        np.savez(
            spec["out"], have=np.asarray(have, np.int64),
            scores=np.concatenate([res["first"][i] for i in have])
            if have else np.zeros((0,), np.float32),
            offsets=np.cumsum([0] + [len(res["first"][i]) for i in have]),
            repeat_gap=res["repeat_gap"], latency=res["latency"],
            attempted=sum(c["attempted"] for c in res["counts"]),
            failed=sum(c["failed"] for c in res["counts"]),
            examples=sum(c["examples"] for c in res["counts"]),
            wall0=res["wall0"], span_s=res["span_s"])
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
