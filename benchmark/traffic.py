"""Traffic of every cell, generated from ``--seed`` by ONE general
generator per entry kind, driven by the parameters in
``workloads/<cell>.json``. A later PR adds a cell by adding such a
file, never code here.

Training: :func:`train_rows` makes the rows of a classification job.
Serving: :func:`request_plan` draws prompts, output lengths and due
times; :func:`drive` plays a plan against ``/generate`` with a
streaming NDJSON client on raw asyncio sockets, closed or open loop,
and times every request from when it was DUE.

Every seed gets the same multiset of sizes and of arrival gaps (drawn
from a fixed base seed in the cell's file); ``--seed`` deals both out
in an order of its own and writes the tokens, so that two seeds offer
the same work at the same mean rate with other arrival times.
"""

from __future__ import annotations

import asyncio
import json
import math
import time

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  int(seed) >> 32, *stream])


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole numbers from one of the length distributions a cell
    file may name: ``uniform`` [lo, hi], ``loguniform`` [lo, hi],
    ``lognormal`` (median, sigma, clipped to [lo, hi]), ``fixed``."""
    kind = spec["dist"]
    lo, hi = spec.get("lo"), spec.get("hi")
    if kind == "fixed":
        v = np.full(n, spec["value"])
    elif kind == "uniform":
        v = rng.integers(lo, hi + 1, n)
    elif kind == "loguniform":
        v = np.exp(rng.uniform(math.log(lo), math.log(hi + 1), n))
    elif kind == "lognormal":
        v = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    v = np.floor(v).astype(np.int64)
    if lo is not None:
        v = np.clip(v, lo, hi)
    return v


# -- training ---------------------------------------------------------------
def train_rows(spec: dict, seed: int, vocab_size: int):
    """``rows`` tokenised sentences of ``seq_len`` positions for a
    two-class job: [CLS] id 101, ``length`` word ids drawn uniformly
    from [1000, vocab), [SEP] id 102, pad id 0 to the end; one planted
    polarity id decides the label; ``positive_share`` of the rows are
    of class 1. Rows all differ (random ids)."""
    rng = _rng(seed, 1)
    n, l = spec["rows"], spec["seq_len"]
    lens = draw_lengths(spec["length"], n, rng)
    lens = np.clip(lens, 3, l - 2)
    x = np.zeros((n, l), np.int32)
    y = (rng.random(n) < spec.get("positive_share", 0.5)).astype(np.int32)
    body = rng.integers(1000, vocab_size, (n, l)).astype(np.int32)
    cols = np.arange(l)[None, :]
    x = np.where(cols <= lens[:, None], body, 0).astype(np.int32)
    x[:, 0] = 101
    x[np.arange(n), lens + 1] = 102
    # the planted polarity word: one of ten ids per class, at a random
    # position inside the sentence
    pos = 1 + (rng.integers(0, 1 << 30, n) % lens)
    x[np.arange(n), pos] = 2000 + 10 * y + rng.integers(0, 10, n)
    return x, y


# -- serving ------------------------------------------------------------------
PRINTABLE = np.arange(32, 127)  # one ASCII byte = one ByteTokenizer token


def request_plan(spec: dict, seed: int, seconds: float) -> list[dict]:
    """The requests of one run: ``prompt`` text, ``n_new`` and, for an
    open loop, ``due`` seconds after the start.

    The multiset of sizes and of gaps between arrivals is drawn ONCE
    from ``spec["base_seed"]``, the ramp and the window each their
    own, so that the window's offered work is the same in every run.
    ``--seed`` deals the lengths AND the gaps out in orders of its own
    (so its arrival times are its own) and writes the prompts'
    characters. Open loop: exponential gaps at ``rate_per_s``
    (Poisson arrivals), scaled so that the ramp's fill ``ramp_s`` and
    the window's fill ``seconds``; closed loop and burst: ``requests``
    entries that the clients take in turn."""
    rng = _rng(seed, 11)

    def part(stream: int, n: int, span: float | None):
        base = _rng(spec["base_seed"], stream)
        p_len = draw_lengths(spec["prompt_tokens"], n, base)
        n_new = draw_lengths(spec["max_new_tokens"], n, base)
        due = None
        if span is not None:
            gaps = base.exponential(1.0, n)[rng.permutation(n)]
            due = np.cumsum(gaps * (span / gaps.sum())) - gaps[0] * (
                span / gaps.sum())
        order = rng.permutation(n)
        return p_len[order], n_new[order], due

    if spec["loop"] == "open":
        ramp = float(spec.get("ramp_s", 0.0))
        n_r = int(round(spec["rate_per_s"] * ramp))
        n_w = max(1, int(round(spec["rate_per_s"] * seconds)))
        parts = []
        if n_r:
            parts.append(part(5, n_r, ramp))
        pl, nn, due = part(7, n_w, seconds)
        parts.append((pl, nn, due + ramp))
        p_len = np.concatenate([p[0] for p in parts])
        n_new = np.concatenate([p[1] for p in parts])
        due = np.concatenate([p[2] for p in parts])
    elif spec["loop"] in ("closed", "burst"):
        p_len, n_new, due = part(7, int(spec["requests"]), None)
    else:
        raise ValueError(f"unknown loop {spec['loop']!r}")
    plan = []
    for i in range(len(p_len)):
        chars = rng.choice(PRINTABLE, int(p_len[i])).astype(np.uint8)
        plan.append({
            "idx": i, "prompt": chars.tobytes().decode("ascii"),
            "n_new": int(n_new[i]),
            "due": float(due[i]) if due is not None else None,
        })
    return plan


class Outcome:
    """What the client saw of one request, on its own clock."""

    __slots__ = ("idx", "due", "sent", "first", "last", "done", "status",
                 "ids", "chunk_ids", "error", "n_new", "frames")

    def __init__(self, idx: int, n_new: int):
        self.idx, self.n_new = idx, n_new
        self.due = self.sent = self.first = self.last = self.done = None
        self.status, self.ids, self.chunk_ids = None, None, []
        self.error, self.frames = None, 0

    @property
    def ok(self) -> bool:
        """Completed at full length, and the frames add up."""
        return (self.error is None and self.status == 200
                and self.ids is not None and len(self.ids) == self.n_new
                and self.chunk_ids == self.ids)


async def _one(host: str, port: int, req: dict, out: Outcome,
               timeout: float) -> None:
    """One streaming ``POST /generate`` on a raw socket."""
    body = json.dumps({"text": req["prompt"], "max_new_tokens": req["n_new"],
                       "stream": True, "temperature": 0.0}).encode()
    head = (f"POST /generate HTTP/1.1\r\nhost: {host}\r\n"
            "content-type: application/json\r\n"
            f"content-length: {len(body)}\r\nconnection: close\r\n\r\n").encode()
    writer = None
    try:
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(host, port), timeout)
        out.sent = time.time()
        writer.write(head + body)
        await writer.drain()
        status_line = await asyncio.wait_for(reader.readline(), timeout)
        out.status = int(status_line.split()[1])
        chunked = False
        while True:
            line = await asyncio.wait_for(reader.readline(), timeout)
            if line in (b"\r\n", b"\n", b""):
                break
            if line.lower().startswith(b"transfer-encoding") and b"chunked" in line.lower():
                chunked = True
        buf = b""

        async def frames():
            nonlocal buf
            while True:
                if chunked:
                    size_line = await asyncio.wait_for(reader.readline(), timeout)
                    if not size_line:
                        return
                    size = int(size_line.strip() or b"0", 16)
                    if size == 0:
                        return
                    data = await asyncio.wait_for(
                        reader.readexactly(size + 2), timeout)
                    buf += data[:-2]
                else:
                    data = await asyncio.wait_for(reader.read(65536), timeout)
                    if not data:
                        return
                    buf += data
                while b"\n" in buf:
                    one, buf = buf.split(b"\n", 1)
                    if one.strip():
                        yield one

        async for raw in frames():
            now = time.time()
            frame = json.loads(raw)
            out.frames += 1
            if "error" in frame:
                out.error = f"{frame.get('code', '')} {frame['error']}"[:200]
                break
            if frame.get("done"):
                out.ids = [int(t) for t in frame["token_ids"]]
                out.done = now
                break
            if frame.get("token_ids"):
                if out.first is None:
                    out.first = now
                out.last = now
                out.chunk_ids.extend(int(t) for t in frame["token_ids"])
        if out.status != 200 and out.error is None:
            out.error = f"http {out.status}"
        if out.done is None and out.error is None:
            out.error = "stream ended without a done frame"
    except (OSError, asyncio.TimeoutError, asyncio.IncompleteReadError,
            ValueError, IndexError) as e:
        out.error = f"{type(e).__name__}: {e}"[:200]
    finally:
        if writer is not None:
            writer.close()
            try:
                await writer.wait_closed()
            except OSError:
                pass


async def drive(host: str, port: int, spec: dict, plan: list[dict],
                seconds: float, *, on_window=None, settle_s: float = 60.0,
                timeout: float = 120.0) -> dict:
    """Play ``plan``. Open loop: each request is sent when it is due,
    whatever has come back (lateness of the generator is recorded).
    Closed loop: ``clients`` workers each send their next request when
    the last one has answered; the clients start one by one over
    ``ramp_s``. The measured window is ``[t0 + ramp_s, t0 + ramp_s +
    seconds)``; ``on_window(t_open)`` is awaited as it opens.
    Requests due (open) or sent (closed) inside it are the run's
    requests; every one of them is waited for, ``settle_s`` past the
    close at most."""
    ramp = float(spec.get("ramp_s", 0.0))
    outcomes: list[Outcome] = []
    t0 = time.time() + 0.05
    t_open, t_close = t0 + ramp, t0 + ramp + seconds
    tasks: list[asyncio.Task] = []

    async def opener():
        await asyncio.sleep(max(0.0, t_open - time.time()))
        if on_window is not None:
            await on_window(t_open)

    open_task = asyncio.create_task(opener())
    if spec["loop"] == "open":
        for req in plan:
            due = t0 + req["due"]
            if due >= t_close:
                break
            delay = due - time.time()
            if delay > 0:
                await asyncio.sleep(delay)
            out = Outcome(req["idx"], req["n_new"])
            out.due = due
            outcomes.append(out)
            tasks.append(asyncio.create_task(
                _one(host, port, req, out, timeout)))
    elif spec["loop"] == "burst":
        # every request of the plan at once, each waited for (warm-up)
        for req in plan:
            out = Outcome(req["idx"], req["n_new"])
            out.due = time.time()
            outcomes.append(out)
            tasks.append(asyncio.create_task(
                _one(host, port, req, out, timeout)))
    else:
        it = iter(plan)
        n_clients = int(spec["clients"])

        async def client(k: int):
            await asyncio.sleep(max(0.0, t0 + ramp * k / n_clients
                                    - time.time()))
            while time.time() < t_close:
                req = next(it, None)
                if req is None:
                    return
                out = Outcome(req["idx"], req["n_new"])
                out.due = time.time()
                outcomes.append(out)
                await _one(host, port, req, out, timeout)

        tasks = [asyncio.create_task(client(k)) for k in range(n_clients)]
    await asyncio.sleep(max(0.0, t_close - time.time()))
    if tasks:
        _, pending = await asyncio.wait(tasks, timeout=settle_s)
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    await open_task
    return {"t0": t0, "t_open": t_open, "t_close": t_close,
            "outcomes": outcomes}


def summarise(result: dict, quantile) -> dict:
    """End-to-end numbers of one run from the client's clock. The
    run's requests are those due inside the window; a request that
    failed, was shed, came back short or never finished counts in
    ``failed`` and, for a tail, as slower than every other."""
    t_open, t_close = result["t_open"], result["t_close"]
    window = t_close - t_open
    mine = [o for o in result["outcomes"] if t_open <= o.due < t_close]
    ok = [o for o in mine if o.ok]
    inf = float("inf")
    ttft = sorted((o.first - o.due) * 1e3 if o.ok else inf for o in mine)
    tpot = sorted(
        ((o.last - o.first) / (len(o.ids) - 1) * 1e3
         if o.ok and len(o.ids) > 1 else inf) for o in mine)
    late = sorted((o.sent - o.due) * 1e3 for o in mine if o.sent is not None)
    done_in = [o for o in result["outcomes"]
               if o.ok and t_open <= o.done < t_close]
    return {
        "attempted": len(mine), "failed": len(mine) - len(ok),
        "window_s": window,
        "out_tokens_per_s": sum(len(o.ids) for o in done_in) / window,
        "completed_in_window": len(done_in),
        "tokens_ok": sum(len(o.ids) for o in ok),
        "ttft_p50_ms": quantile(ttft, 0.5), "ttft_p95_ms": quantile(ttft, 0.95),
        "tpot_p50_ms": quantile(tpot, 0.5), "tpot_p95_ms": quantile(tpot, 0.95),
        "loadgen_late_p95_ms": quantile(late, 0.95),
        "errors": sorted({o.error for o in mine if o.error})[:5],
    }
