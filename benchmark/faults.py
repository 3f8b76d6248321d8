"""Faults planted in the timed path, for the tests under ``tests/``
that have to see ``correct`` come out false (step 3 of "How correct is
decided"). A benchmark run never imports this file: the drivers pass a
fault name to a child only in a rehearsal, from ``BENCH_TEST_FAULT``.
"""

from __future__ import annotations


def plant_train(fault: str, loop_module) -> None:
    """Break the step that ``fit`` is about to build. Wraps whatever
    ``make_train_step`` is installed (the harness's own wrapper sits
    outside, so it sees the broken step as the program's)."""
    inner_make = loop_module.make_train_step

    def make(*a, **kw):
        step = inner_make(*a, **kw)
        if fault == "state_unchanged":
            def broken(params, opt_state, x, y):
                _, _, loss = step(
                    *_copies(params, opt_state), x, y)
                return params, opt_state, loss
        elif fault == "drop_half":
            def broken(params, opt_state, x, y):
                h = x.shape[0] // 2
                return step(params, opt_state, x[:h], y[:h])
        elif fault == "quarter_batch":
            # the first of four shards alone: the update a replica of a
            # (4, 1) mesh applies where its gradient is not all-reduced
            def broken(params, opt_state, x, y):
                q = x.shape[0] // 4
                return step(params, opt_state, x[:q], y[:q])
        else:
            raise ValueError(f"unknown training fault {fault!r}")
        broken.lower = step.lower
        return broken

    loop_module.make_train_step = make


def _copies(params, opt_state):
    """The real step donates its state; give it copies so that the
    caller's survive unchanged."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.copy, (params, opt_state))


def plant_serve(fault: str) -> None:
    """``alter_token``: every chunk of tokens a request is handed has
    its first id replaced, where it is produced (``GenRequest.push``)."""
    if fault != "alter_token":
        raise ValueError(f"unknown serving fault {fault!r}")
    from mlapi_tpu.serving import requests

    real = requests.GenRequest.push

    def push(self, item):
        if isinstance(item, dict) and item.get("token_ids"):
            ids = list(item["token_ids"])
            ids[0] = (int(ids[0]) + 7) % 200 + 4
            item = {**item, "token_ids": ids}
        return real(self, item)

    requests.GenRequest.push = push
