"""Chained device dispatch with lazy token drains.

``decode_chunk_fn`` RETURNS the feedback token as a device array, so
consecutive chunks need no host round trip between them: the decode
loop dispatches ahead and drains token readbacks lazily. A synced
readback costs a host round trip while argument uploads pipeline for
free, so this turns a request's serial cost from one round trip PER
CHUNK into one readback at the end (how much that buys on a locally
attached chip is ROADMAP D3's question, not yet measured).

:class:`DispatchChain` owns the in-flight chunk queue and the
device-resident feedback token (``tok_dev``); the per-request delivery
bookkeeping stays with the caller as the ``deliver`` callback, because
it mutates the batch's host mirrors. Anything that mutates batch state
— admission, compaction, the spec phase — must :meth:`invalidate`
first (drain fully and drop the device chain: the host mirrors are the
source of truth again). Split out of ``engine._run_batch`` (r04
VERDICT "Next" #7).
"""

from __future__ import annotations

import numpy as np

from mlapi_tpu.utils.metrics import span


class DispatchChain:
    def __init__(self, deliver, sums):
        # deliver(toks_host [B, size], size, live_indices): push the
        # drained chunk to its requests and update the host mirrors.
        self._deliver = deliver
        # Where the readback wait is summed (the engine's
        # ``LatencyStats.sums``).
        self._sums = sums
        self._inflight: list = []  # (toks_dev [B, size], size, live)
        self.tok_dev = None        # device-resident feedback token

    def __len__(self) -> int:
        return len(self._inflight)

    def push(self, toks_dev, size: int, live: list) -> None:
        """Queue one dispatched chunk's device output for a later
        drain. ``live`` are the request indices it covers."""
        self._inflight.append((toks_dev, size, live))

    def pending_live(self):
        """Request indices covered by any in-flight chunk."""
        for _, _, plive in self._inflight:
            yield from plive

    def drain(self, count: int | None = None) -> None:
        """Read back the oldest ``count`` chunks (all by default) and
        deliver them in dispatch order."""
        take = self._inflight[:] if count is None else self._inflight[:count]
        if not take:
            return
        del self._inflight[: len(take)]
        for toks_dev, _, _ in take:
            # Start every host copy before blocking on the first: one
            # overlapped transfer window instead of a serial RTT per
            # chunk. (A device-side concat + single readback was
            # tried too and landed in the same noise band, so the
            # simpler form stays.)
            try:
                toks_dev.copy_to_host_async()
            except AttributeError:
                pass
        for toks_dev, got, plive in take:
            # The host blocks HERE until the device has produced the
            # chunk: the dispatch thread's wait for the device (the
            # delivery that follows is host work, outside the span).
            with span("sched.readback", "readback_wait",
                      registry=self._sums, chunks=len(take)):
                toks_host = np.asarray(toks_dev)
            self._deliver(toks_host, got, plive)

    def invalidate(self) -> None:
        """Batch state is about to change under the chain: deliver
        everything in flight and drop the device-resident feedback
        token — the next dispatch re-uploads from the host mirrors."""
        self.drain()
        self.tok_dev = None
