"""Pluggable collectives for the distributed power method.

The paper's whole efficiency argument (Table 1) is that only O(d+m)
power-iteration vectors cross the wire per round. A ``Reducer`` makes the
*encoding* of those vectors a tunable axis: the power method asks it to sum
the workers' local contributions (``A_j v`` / ``A_j^T u``) over the data
mesh, and the reducer decides what actually hits the network —

    ``dense``    exact f32 psum (today's behavior, the paper's master),
    ``int8``     stochastic-rounding quantize -> s8 psum -> dequantize,
                 one f32 scale pmax per vector (``comm/int8.py``),
    ``topk:r``   magnitude sparsification with per-worker error-feedback
                 residuals, index+value all-gather (``comm/topk.py``).

Only the power-iteration *vector* psums are rerouted; the epoch's scalar
psums (loss, <W, grad>, line-search terms) stay exact — compressing a
handful of f32 scalars saves nothing and silently corrupts step sizes and
the duality-gap certificate.

State contract: ``init_state(d, m)`` returns a per-worker pytree (empty for
stateless reducers) that the caller threads through every ``exchange`` call —
through the epoch's ``fori_loop`` and across epochs as part of the sharded
state (each worker keeps its own residuals). ``exchange`` is pure and works
serially (``axis_name=None``: the "sum" over one worker, with compression
noise still applied — the serial run simulates the distributed encoding) and
inside shard_map.

The reducer answers *how bytes are encoded*; *what graph they flow over* is
the ``Topology`` axis (``comm/topology.py``), whose ``all_reduce`` mirrors
``exchange`` — a ``hier:<g>`` topology runs a reducer on its inter-group hop
only, by passing ``groups=`` (XLA ``axis_index_groups``) through the helpers
below.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, List, Optional, Sequence, Union

import jax

from ..specs import CommSpec, parse_comm  # noqa: F401

AxisName = Optional[Union[str, Sequence[str]]]
Groups = Optional[List[List[int]]]
PyTree = Any


class Reducer:
    """Interface of a compressed collective (see module docstring).

    ``spec`` is the parseable name (``make_reducer(r.spec)`` round-trips).
    """

    spec: str = "base"

    def init_state(self, d: int, m: int) -> PyTree:
        """Per-worker reducer state for (d,)-slot "u" and (m,)-slot "v"."""
        return ()

    def state_spec(self, d: int, m: int) -> PyTree:
        """Structure/shape/dtype of ONE worker's state, as a pytree of
        ``jax.ShapeDtypeStruct`` — no allocation. This is the reducer's
        save/restore contract: checkpoints store the state with a leading
        worker axis prepended to every leaf, restore skeletons are built
        from this spec, and an elastic remesh (worker count change)
        re-*initializes* via ``init_state`` rather than re-sharding —
        residuals are per-worker quantities that cannot follow a data
        repartition. The default derives the spec from ``init_state``;
        stateful reducers should override it to avoid the allocation."""
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            self.init_state(d, m),
        )

    def exchange(
        self,
        x: jax.Array,
        state: PyTree,
        *,
        slot: str,
        key: jax.Array,
        axis_name: AxisName = None,
        weight=None,
        groups: Groups = None,
    ) -> tuple:
        """Sum local contributions ``x`` over ``axis_name``.

        ``slot`` ("u" | "v") names which per-shape buffer of ``state``
        belongs to this call; ``key`` feeds stochastic encodings and must
        differ per call (the caller folds the iteration index in). Returns
        ``(global_sum_estimate, new_state)``.

        ``weight`` is the caller's straggler mask for this worker (``x`` is
        already scaled by it; ``None`` means full participation). Stateless
        reducers can ignore it — a masked worker's ``x`` is exactly zero —
        but *stateful* ones must: a sampled-out worker has to contribute
        nothing this round (not its stale residual) and leave its state
        untouched, or the driver's unbiased-reweighting argument breaks.

        ``groups`` (XLA ``axis_index_groups``: a partition of the axis
        indices) restricts the sum to each worker's own group — how a
        ``hier`` topology runs the encoded exchange on the inter-group hop
        only. ``None`` sums over the whole axis.
        """
        raise NotImplementedError

    def reduce(self, x, state, *, slot, key, axis_name=None, weight=None,
               groups=None):
        """Deprecated pre-topology name for :meth:`exchange` (warns once)."""
        _warn_reduce_deprecated()
        return self.exchange(
            x, state, slot=slot, key=key, axis_name=axis_name, weight=weight,
            groups=groups,
        )

    def wire_bytes(self, dim: int, num_workers: int) -> int:
        """Analytic wire bytes of one ``reduce`` of a (dim,) f32 vector
        (ring all-reduce factor 2x, all-gather 1x of the gathered shape) —
        the extended-Table-1 entries; ``repro.analysis.hlo`` measures the
        same convention."""
        raise NotImplementedError


_REDUCE_DEPRECATION_WARNED = False


def _warn_reduce_deprecated() -> None:
    # Warn once per process, not per call: ``reduce`` sits inside the power
    # method's fori_loop, and a warning per trace step would bury the signal.
    global _REDUCE_DEPRECATION_WARNED
    if not _REDUCE_DEPRECATION_WARNED:
        _REDUCE_DEPRECATION_WARNED = True
        warnings.warn(
            "Reducer.reduce(...) is deprecated; call Reducer.exchange(...) "
            "(same signature — renamed to mirror Topology.all_reduce)",
            DeprecationWarning,
            stacklevel=3,
        )


def psum(x: jax.Array, axis_name: AxisName, groups: Groups = None) -> jax.Array:
    if axis_name is None:
        return x
    return jax.lax.psum(x, axis_name, axis_index_groups=groups)


def pmax(x: jax.Array, axis_name: AxisName, groups: Groups = None) -> jax.Array:
    if axis_name is None:
        return x
    return jax.lax.pmax(x, axis_name, axis_index_groups=groups)


def fold_axis_index(key: jax.Array, axis_name: AxisName) -> jax.Array:
    """Decorrelate per-worker randomness: fold each mesh axis index into the
    (replicated) key. No-op outside shard_map."""
    if axis_name is None:
        return key
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    for name in names:
        key = jax.random.fold_in(key, jax.lax.axis_index(name))
    return key


@dataclasses.dataclass(frozen=True)
class DenseReducer(Reducer):
    """Exact f32 psum — byte-for-byte the paper's master aggregate.

    This is the **default** reducer: the epoch carry
    (``core/frank_wolfe.EpochCarry``) always threads a ``comm_state``, and
    dense's is the empty pytree ``()``, so the serial and sharded drivers
    run one uniform code path under every encoding (``comm="dense"`` routes
    here; its ``reduce`` *is* ``jax.lax.psum``, so trajectories are exact).
    The plumbing itself is validated bit-for-bit against a raw-psum oracle
    in ``tests/test_comm.py``.
    """

    spec = "dense"

    def exchange(self, x, state, *, slot, key, axis_name=None, weight=None,
                 groups=None):
        return psum(x, axis_name, groups), state

    def wire_bytes(self, dim: int, num_workers: int) -> int:
        return 2 * 4 * dim  # ring all-reduce: 2x the f32 vector


def make_reducer(
    spec: str,
    *,
    num_workers: int = 1,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> Reducer:
    """Parse a ``comm=`` spec into a reducer.

    - ``"dense"``   exact psum
    - ``"int8"``    stochastic-rounding s8 psum (needs ``num_workers`` to
                    size the per-worker integer budget 127 // N)
    - ``"topk:r"``  keep the r largest-|.| components per vector, error
                    feedback for the rest

    ``use_pallas``/``interpret`` route the int8 quantize/dequantize pair
    through the ``kernels/quantize`` Pallas kernels (TPU) or the jnp ref.

    The string grammar (and its error messages) lives in
    ``repro.specs.parse_comm``; this function only constructs the object.
    """
    from . import int8 as int8_mod
    from . import topk as topk_mod

    c = parse_comm(spec)
    if c.kind == "dense":
        return DenseReducer()
    if c.kind == "int8":
        return int8_mod.Int8Reducer(
            num_workers=num_workers, use_pallas=use_pallas, interpret=interpret
        )
    return topk_mod.TopKReducer(k=c.k)
