"""Faults planted under the timed path, to show that ``correct`` catches them.

Each fault breaks the program the way a faulty change could, underneath an
otherwise complete run of a cell: ``plant(name, kind)`` returns a context
manager that installs the fault and the keyword arguments to hand the
cell's driver. The benchmark's own runs never plant one; the tests under
``tests/bench`` and ``bench/control.py`` do.

- ``state_unchanged``: every epoch returns its state and iterate as they
  came in (training).
- ``half_batch``: half of the rows are left out and the loss is taken over
  the rest, scaled up as a mean would be (training); half of each
  request's rows are left unscored (serving).
- ``exchange_left_out``: the all-reduce of the power vectors is skipped,
  so every chip follows its own shard (training over several chips). The
  chips' gap estimates then differ, and a chip that stops would leave the
  others waiting in the loss's collectives, so these jobs run to their
  epoch cap with the gap stop off and end in an answer to judge.
- ``answer_altered``: the answer is altered where it is produced: the
  first atom of the returned iterate flips sign (training), one score of
  each request moves by 1% of the request's largest (serving).
"""
from __future__ import annotations

import contextlib
import dataclasses

TRAINING = ("state_unchanged", "half_batch", "exchange_left_out", "answer_altered")
SERVING = ("half_batch", "answer_altered")


def applicable(kind: str, chips: int) -> tuple:
    if kind == "serve":
        return SERVING
    return TRAINING if chips > 1 else tuple(f for f in TRAINING
                                            if f != "exchange_left_out")


@contextlib.contextmanager
def _patched(obj, attr, value):
    old = getattr(obj, attr)
    setattr(obj, attr, value)
    try:
        yield
    finally:
        setattr(obj, attr, old)


def plant(name: str, kind: str):
    """(context manager, driver keyword arguments) for one fault."""
    if kind == "serve":
        return contextlib.nullcontext(), {"engine_factory": _engine(name)}
    from repro.comm import base
    from repro.core import low_rank, tasks
    from repro.launch import dfw

    if name == "state_unchanged":
        stack = contextlib.ExitStack()
        stack.enter_context(_patched(low_rank, "fw_update", lambda it, *a, **k: it))
        stack.enter_context(_patched(low_rank, "fw_update_block",
                                     lambda it, *a, **k: it))
        for t in (tasks.MultinomialLogistic, tasks.MultiTaskLeastSquares):
            stack.enter_context(_patched(t, "update", lambda self, s, *a: s))
        return stack, {}
    if name == "exchange_left_out":
        def fit(task, x, y, *, cfg, **kw):
            return dfw.fit(task, x, y, cfg=dataclasses.replace(cfg, gap_tol=None), **kw)
        return _patched(base.DenseReducer, "exchange",
                        lambda self, x, state, **k: (x, state)), {"fit_fn": fit}
    if name == "half_batch":
        def fit(task, x, y, **kw):
            half = x.shape[0] // 2
            res = dfw.fit(task, x[:half], y[:half], **kw)
            return dataclasses.replace(res, final_loss=2.0 * res.final_loss)
        return contextlib.nullcontext(), {"fit_fn": fit}
    if name == "answer_altered":
        def fit(task, x, y, **kw):
            res = dfw.fit(task, x, y, **kw)
            it = res.iterate
            return dataclasses.replace(res, iterate=it._replace(s=it.s.at[0].multiply(-1.0)))
        return contextlib.nullcontext(), {"fit_fn": fit}
    raise KeyError(name)


def _engine(name: str):
    import numpy as np

    from repro.serve import ServingEngine

    class Altered:
        def __init__(self, pending):
            self._pending = pending

        def block(self):
            out = self._pending.block().copy()
            out[0, 0] += 0.01 * np.max(np.abs(out))
            return out

    class Faulty(ServingEngine):
        def score_async(self, x):
            x = np.array(x, np.float32, ndmin=2)
            if name == "half_batch":
                x[(x.shape[0] + 1) // 2:] = 0.0
            pending = super().score_async(x)
            return Altered(pending) if name == "answer_altered" else pending

    if name not in SERVING:
        raise KeyError(name)
    return Faulty
