"""DFW-TRACE as a first-class framework feature: trace-norm-constrained
classifier / LM-head training on top of any backbone in the model zoo.

This is exactly the paper's ImageNet experiment (frozen ResNet50 features ->
trace-norm multinomial logistic head) transposed to the LM zoo: the backbone
produces d_model features per token; DFW-TRACE learns the (d_model x vocab)
head under ||W||_* <= mu with O(d+V) communication per power iteration.

Distributed execution: features/labels are sharded over the data axes; the
epoch step is the core frank_wolfe epoch wrapped in shard_map (see
``sharded_fit``). The head after T epochs has rank <= T — a certified
low-rank head, storable in factored form.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import lm

from . import engine, frank_wolfe, low_rank, tasks


def extract_features(
    params, batches, cfg, *, max_tokens: Optional[int] = None
) -> Tuple[jax.Array, jax.Array]:
    """Frozen-backbone feature extraction: (X (n, d_model), y (n,))."""
    feats, labels = [], []
    fwd = jax.jit(lambda p, b: lm.forward(p, b, cfg, mode="hidden")["hidden"])
    for batch in batches:
        h = fwd(params, batch)  # (B, S, D)
        b, s, d = h.shape
        feats.append(h.reshape(b * s, d))
        labels.append(jnp.reshape(batch["labels"], (-1,)))
    x = jnp.concatenate(feats)
    y = jnp.concatenate(labels)
    if max_tokens is not None:
        x, y = x[:max_tokens], y[:max_tokens]
    return x.astype(jnp.float32), y


@dataclasses.dataclass
class HeadFitResult:
    iterate: low_rank.FactoredIterate  # factored head, rank <= epochs
    history: Dict[str, list]  # pre-update per-epoch trajectory
    final_loss: float = float("nan")  # loss of the returned head

    def head_matrix(self) -> jax.Array:
        return low_rank.materialize(self.iterate)


def train_head(
    x: jax.Array,  # (n, d) features
    y: jax.Array,  # (n,) int labels
    num_classes: int,
    *,
    mu: float = 30.0,
    num_epochs: int = 50,
    schedule: str = "const:2",
    key: Optional[jax.Array] = None,
) -> HeadFitResult:
    """Single-process DFW-TRACE head fit (paper Fig. 3 setting)."""
    task = tasks.MultinomialLogistic(d=x.shape[1], m=num_classes)
    state = task.init_state(x, y)
    res = frank_wolfe.fit(
        task, state, mu=mu, num_epochs=num_epochs,
        key=key if key is not None else jax.random.PRNGKey(0),
        schedule=schedule, step_size="default",
    )
    return HeadFitResult(iterate=res.iterate, history=res.history,
                         final_loss=res.final_loss)


def sharded_fit(
    mesh: Mesh,
    x: jax.Array,
    y: jax.Array,
    num_classes: int,
    *,
    data_axes=("data",),
    mu: float = 30.0,
    num_epochs: int = 20,
    schedule: str = "const:2",
    key: Optional[jax.Array] = None,
    gap_tol: Optional[float] = None,
    block_epochs: Optional[int] = None,
    checkpointer=None,
    resume=None,
) -> HeadFitResult:
    """DFW-TRACE with the sample axis sharded over ``data_axes`` — the
    production path the multi-pod dry-run lowers. Every epoch's cross-device
    traffic is 2*K psums of (d + m) floats (paper Table 1). Execution is the
    device-resident engine: each constant-K(t) segment is one ``lax.scan``
    inside shard_map, so a ``const:K`` head fit is a single jit dispatch;
    ``gap_tol`` stops on the duality-gap certificate at segment granularity.

    Long head fits are durable like any other DFW-Trace run:
    ``checkpointer`` (``repro.checkpoint.RunCheckpointer``) saves the carry
    at segment boundaries, and ``resume`` (a ``repro.checkpoint.
    RunSnapshot``, e.g. from ``checkpoint.restore_run`` with the sharded
    ``LogisticState`` as ``state_like``) continues a previous fit from its
    saved epoch — the restored global state is re-placed onto *this* mesh,
    so resuming onto a different worker count is the elastic path.
    """
    task = tasks.MultinomialLogistic(d=x.shape[1], m=num_classes)
    ax = data_axes if len(data_axes) > 1 else data_axes[0]
    state_specs = tasks.LogisticState(x=P(ax), y=P(ax), z=P(ax))
    wrapper = engine.shard_map_segment_wrapper(mesh, ax, state_specs)

    state = task.init_state(
        jax.device_put(x, NamedSharding(mesh, P(ax))),
        jax.device_put(y, NamedSharding(mesh, P(ax))),
    )
    iterate, start_t, initial_history = None, 0, None
    if resume is not None:
        state = jax.tree.map(
            lambda a, s: jax.device_put(jnp.asarray(a), NamedSharding(mesh, s)),
            resume.carry.state, state_specs,
        )
        key = jnp.asarray(resume.carry.key)
        start_t, initial_history = resume.t, resume.history
        # Capacity must hold the checkpoint's live factors even when the
        # checkpoint already covers (or exceeds) the requested budget — the
        # finished-run return below still needs the unpacked iterate.
        iterate = resume.unpack_iterate(
            engine.resolve_max_rank(None, max(num_epochs, start_t))
        )
        if start_t >= num_epochs:
            # The checkpoint already covers the requested budget (the final
            # boundary is always saved): return it rather than asking the
            # engine for zero epochs.
            final_loss, _ = engine.final_loss(task, state)
            return HeadFitResult(iterate=iterate, history=resume.history,
                                 final_loss=final_loss)
    if checkpointer is not None:
        # Same contract as launch/dfw's drivers: the store is this run's
        # timeline — steps past start_t (all steps, for a fresh fit) would
        # shadow the new history on a later default latest-step restore.
        checkpointer.store.discard_after(start_t)
    res = frank_wolfe.fit(
        task, state, mu=mu, num_epochs=num_epochs,
        key=key if key is not None else jax.random.PRNGKey(0),
        schedule=schedule, step_size="default",
        axis_name=ax,
        segment_wrapper=wrapper,
        gap_tol=gap_tol,
        block_epochs=block_epochs,
        iterate=iterate,
        start_t=start_t,
        initial_history=initial_history,
        checkpointer=checkpointer,
    )
    return HeadFitResult(iterate=res.iterate, history=res.history,
                         final_loss=res.final_loss)


def top_k_error(
    it: low_rank.FactoredIterate, x: jax.Array, y: jax.Array, k: int = 5
) -> float:
    """Paper's top-5 misclassification metric, factored-head evaluation."""
    logits = low_rank.right_multiply(it, x)
    _, idx = jax.lax.top_k(logits, k)
    hit = jnp.any(idx == y[:, None], axis=-1)
    return float(jax.device_get(1.0 - jnp.mean(hit.astype(jnp.float32))))
