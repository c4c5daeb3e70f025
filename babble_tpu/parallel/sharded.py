"""Sharding layout + the sharded end-to-end consensus step.

Layout (annotate-and-let-XLA-partition, the pjit recipe):

- per-event vectors (sp, op, creator, seq, ts, mbit, round, witness, rr,
  cts): split along the event axis → ``P("ev")``.
- coordinate matrices la/fd ``[E+1, N]``: event rows over "ev", participant
  columns over "p" → ``P("ev", "p")``.  StronglySee's compare-count
  reduction then runs as per-shard partial counts + an ICI psum over "p"
  (inserted by XLA from the sharding constraints).
- witness tables wslot/famous ``[R+1, N]``: rounds replicated, creator
  columns over "p" → ``P(None, "p")`` (every round is touched by the fame
  scan each step; the N axis is where the width is at 10k participants).
- creator tables ce/cnt (+1-row sentinel shapes, small: ~N·S int32) and
  scalars + ingest batches: replicated.

Explicit shardings must divide the array dims, so ``pad_cfg_for_mesh``
rounds the event capacity up to a multiple of the "ev" axis (keeping the
+1 sentinel row) and pads the participant width to a multiple of "p" with
dead columns — sentinel coordinates (la=-1, fd=INT32_MAX) make padded
participants invisible to every see/vote count, and DagConfig.n_real keeps
the supermajority + coin-round thresholds on the true count.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..ops.fame import decide_fame_auto_impl
from ..ops.ingest import EventBatch, ingest_impl
from ..ops.order import decide_order_impl
from ..ops.state import DagConfig, DagState, init_state


def state_specs() -> DagState:
    """DagState-shaped pytree of PartitionSpecs."""
    ev = P("ev")
    return DagState(
        sp=ev, op=ev, creator=ev, seq=ev, ts=ev, mbit=ev,
        la=P("ev", "p"), fd=P("ev", "p"),
        round=ev, witness=ev, rr=ev, cts=ev,
        ce=P(), cnt=P(),
        wslot=P(None, "p"), famous=P(None, "p"),
        sm=P(),
        # packed witness bitplanes (kernel diet): REPLICATED.  The
        # uint8 lane axis is ceil(n/8) — 8 participant columns per
        # lane — so "p" rarely divides it (it divides n, not n/8), and
        # at [R+1, ceil(N/8)] bytes the planes are ~1/32768th of one
        # fd tensor at 10k participants: replication costs nothing and
        # keeps the lane math local to every shard
        mbr=P(), fmr=P(),
        n_events=P(), max_round=P(), lcr=P(),
        e_off=P(), s_off=P(), r_off=P(),
    )


def state_shardings(mesh: Mesh) -> DagState:
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), state_specs(),
        is_leaf=lambda x: isinstance(x, P),
    )


def batch_shardings(mesh: Mesh) -> EventBatch:
    """Ingest batches are small relative to state: replicate them."""
    rep = NamedSharding(mesh, P())
    return EventBatch(
        sp=rep, op=rep, creator=rep, seq=rep, ts=rep, mbit=rep, k=rep,
        sched=rep,
    )


def place_state(state: DagState, mesh: Mesh) -> DagState:
    return jax.device_put(state, state_shardings(mesh))


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def pad_cfg_for_mesh(cfg: DagConfig, mesh: Mesh) -> DagConfig:
    """Round capacities up so every sharded dim divides its mesh axis."""
    ev = mesh.shape["ev"]
    p = mesh.shape["p"]
    n_pad = _ceil_to(cfg.n, p)
    e_cap = _ceil_to(cfg.e_cap + 1, ev) - 1
    n_real = cfg.n_real or cfg.n
    return DagConfig(
        n=n_pad, e_cap=e_cap, s_cap=cfg.s_cap, r_cap=cfg.r_cap,
        n_real=n_real, coord16=cfg.coord16, coord8=cfg.coord8,
        packed=cfg.packed,
    )


def consensus_step_impl(
    cfg: DagConfig, fd_mode: str, state: DagState, batch: EventBatch,
    batch_window: bool = True,
) -> DagState:
    """The full step: ingest a gossip batch, then run the whole consensus
    pipeline (DivideRounds ≡ ingest's round scan, DecideFame, FindOrder's
    device half).  This is the framework's 'training step' — the unit the
    multichip dry-run jits over a mesh.

    ``batch_window`` (static) asserts the all-window-offsets-zero
    invariant of fresh batch states, which lets wide-N fame use the
    one-hot MXU strongly-see (ops/ss.py).  A rolled-window caller (none
    exists today — the live engine drives its own phase calls with
    batch_window=False) MUST pass False here or wide-N fame miscounts.

    The phases run under the live flush's ``named_scope`` names, which
    reach the compiled program's ``op_name`` metadata (the v5e trace's
    operations carry none: ``benchmark/scopes.py`` joins the two)."""
    with jax.named_scope("babble_ingest"):
        state = ingest_impl(cfg, state, fd_mode, batch)
    with jax.named_scope("babble_fame"):
        state = decide_fame_auto_impl(cfg, state, batch_window)
    with jax.named_scope("babble_order"):
        return decide_order_impl(cfg, state)


def make_sharded_step(cfg: DagConfig, mesh: Mesh, fd_mode: str = "full"):
    """Jit the full consensus step with mesh shardings annotated in/out."""
    ss = state_shardings(mesh)
    return jax.jit(
        functools.partial(consensus_step_impl, cfg, fd_mode),
        in_shardings=(ss, batch_shardings(mesh)),
        out_shardings=ss,
        donate_argnums=(0,),
    )


def sharded_init_state(cfg: DagConfig, mesh: Mesh) -> DagState:
    return place_state(init_state(cfg), mesh)


# ----------------------------------------------------------------------
# byzantine (fork) pipeline sharding: the branch-column axis B = n*k is
# the wide dimension; partition it over "p" exactly like the honest N
# axis.  The creator-grouped reductions (strided OR over the k branch
# slots) contract B -> N, so "p" must divide n (then it divides B=k*n);
# strongly-see counts then run as per-shard partials + psum, inserted by
# XLA from the sharding constraints.


def fork_batch_specs():
    from ..ops.forks import ForkBatch

    ev = P("ev")
    return ForkBatch(
        sp=ev, op=ev, ebr=ev, eseq=ev, ecr=ev, ts=ev, mbit=ev,
        sched=P(), cp=P("p", None), ce=P("p", None), cnt=P("p"),
        owner=P("p", None), n_events=P(),
        rseed=ev, wseed=ev, s_off=P("p"),
    )


def fork_out_specs():
    from ..ops.forks import ForkOut

    ev = P("ev")
    return ForkOut(
        la=P("ev", "p"), det=P("ev", None), fd=P("ev", "p"),
        round=ev, witness=ev, wslot=P(None, "p"), famous=P(None, "p"),
        rr=ev, cts=ev, max_round=P(), lcr=P(),
        closure_steps=P(), vote_steps=P(), band_fallbacks=P(),
    )


def pad_fork_for_mesh(cfg, batch, mesh: Mesh):
    """Round the fork batch's event axis up so e_cap+1 divides the "ev"
    mesh axis.  Padding rows replicate the sentinel (sp=-1, eseq=-1 ...),
    so they are invisible; the old sentinel row just becomes one more
    dead event row."""
    from ..ops.forks import ForkBatch

    ev = mesh.shape["ev"]
    e1_new = _ceil_to(cfg.e_cap + 1, ev)
    if e1_new == cfg.e_cap + 1:
        return cfg, batch
    pad = e1_new - (cfg.e_cap + 1)

    def pad1(a, fill):
        return jnp.concatenate(
            [a, jnp.full((pad,), fill, a.dtype)]
        )

    batch = batch._replace(
        sp=pad1(batch.sp, -1), op=pad1(batch.op, -1),
        ebr=pad1(batch.ebr, cfg.b), eseq=pad1(batch.eseq, -1),
        ecr=pad1(batch.ecr, cfg.n), ts=pad1(batch.ts, 0),
        mbit=pad1(batch.mbit, False),
        rseed=pad1(batch.rseed, -1), wseed=pad1(batch.wseed, -1),
    )
    return cfg._replace(e_cap=e1_new - 1), batch


def make_sharded_fork_step(cfg, mesh: Mesh):
    """Jit the whole fork pipeline with mesh shardings annotated."""
    from ..ops.forks import fork_pipeline_impl

    if cfg.n % mesh.shape["p"]:
        raise ValueError(
            f"mesh 'p'={mesh.shape['p']} must divide creators n={cfg.n}"
        )
    to_shard = lambda tree: jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), tree,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.jit(
        functools.partial(fork_pipeline_impl, cfg),
        in_shardings=(to_shard(fork_batch_specs()),),
        out_shardings=to_shard(fork_out_specs()),
    )
