"""``faults.py``'s two training faults for a step that may return
values beside ``(params, opt_state, loss)`` and may be fed ONE row
(``faults.plant_train`` unpacks three values and halves the rows). A
benchmark run never imports this file: ``train_lm_child.py`` does so
only in a rehearsal, from ``BENCH_TEST_FAULT``."""

from __future__ import annotations

import faults


def plant_train(fault: str, loop_module) -> None:
    """Break the step that ``fit`` is about to build, under the
    harness's own wrapper. ``drop_half`` trains on half of the rows,
    or of one row's positions (``reference/*.py`` ``train_steps`` does
    the same under that name)."""
    inner_make = loop_module.make_train_step

    def make(*a, **kw):
        step = inner_make(*a, **kw)
        if fault == "state_unchanged":
            def broken(params, opt_state, x, y):
                _, _, *rest = step(*faults._copies(params, opt_state), x, y)
                return (params, opt_state, *rest)
        elif fault == "drop_half":
            def broken(params, opt_state, x, y):
                if x.shape[0] > 1:
                    h = x.shape[0] // 2
                    return step(params, opt_state, x[:h], y[:h])
                h = x.shape[1] // 2
                return step(params, opt_state, x[:, :h], y[:, :h])
        else:
            raise ValueError(f"unknown training fault {fault!r}")
        broken.lower = step.lower
        return broken

    loop_module.make_train_step = make
