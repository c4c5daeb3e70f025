"""Driver ``replay``: a recorded DAG ordered whole by the batch path
(``cli.sim_step``, the fused ingest + fame + order step), step after
step from a fresh state, for the window.

The run's seed draws the DAG from the benchmark's own generator, which
gives every seed the same sizes (every validator mints as many events)
in another order.  The program's level schedule (one row per
topological level, at most one event per validator) is padded with
empty rows to the configuration's ``sched_rows`` x participants, so
every seed runs the one compiled program on the same shapes.  Each step starts from the same fresh
state and orders the whole DAG.  After the window, a sample of the
window's steps drawn from the seed, the last step among them, is
compared event by event with the plain reference."""

from __future__ import annotations

import sys
import time

#: steps of the window whose output is compared with the reference
SAMPLE = 8


def run(ctx) -> dict:
    import jax
    import numpy as np

    from babble_tpu.cli import sim_step
    from babble_tpu.ops.state import init_state
    from babble_tpu.sim.arrays import ArrayDag, batch_from_arrays

    from benchmark.reference import hashgraph, native

    conf = ctx.config
    n, e = conf["participants"], conf["events"]
    dag = native.gossip_dag(n, e, ctx.seed)
    adag = ArrayDag(n, dag["sp"], dag["op"], dag["creator"], dag["seq"],
                    dag["ts"], dag["mbit"], dag["levels"], ctx.seed)
    cfg, step = sim_step(adag, conf["r_cap"], conf["ingest"])
    batch = batch_from_arrays(adag)
    sched = np.asarray(batch.sched)
    rows, width = conf["sched_rows"], n
    if sched.shape[0] > rows or sched.shape[1] > width:
        raise ValueError(f"the DAG's schedule {sched.shape} exceeds "
                         f"{rows} x {width}")
    padded = np.full((rows, width), -1, np.int32)
    padded[:sched.shape[0], :sched.shape[1]] = sched
    batch = batch._replace(sched=jax.numpy.asarray(padded))
    state = jax.block_until_ready(init_state(cfg))
    compiled = step.lower(state, batch).compile()
    jax.block_until_ready(compiled(state, batch))     # warm step

    def decisions(o):
        return (o.round, o.witness, o.wslot, o.famous, o.rr, o.cts)

    # a uniform sample of the window's steps (reservoir), from the seed
    rng = np.random.default_rng(ctx.seed % 2**64)
    sample = []
    steps = steps_traced = 0
    ctx.begin_window()
    t_end = ctx.window_t0 + ctx.seconds
    while time.perf_counter() < t_end:
        with ctx.span("bench_replay_step"):
            out = jax.block_until_ready(compiled(state, batch))
        steps += 1
        if len(sample) < SAMPLE - 1:
            sample.append(decisions(out))
        else:
            k = int(rng.integers(steps))
            if k < SAMPLE - 1:
                sample[k] = decisions(out)
        if ctx.trace_due():
            ctx.stop_trace()
            steps_traced = steps
    ctx.end_window()
    steps_traced = steps_traced or steps
    if steps >= SAMPLE:
        sample.append(decisions(out))
    ctx.read_memory()

    got_all = []
    for rnd, wit, wslot, famous, rr, cts in sample:
        got_all.append({
            "round": np.asarray(rnd)[:e], "witness": np.asarray(wit)[:e],
            "fame": hashgraph.fame_per_event(
                np.asarray(wslot), np.asarray(famous), e),
            "rr": np.asarray(rr)[:e], "cts": np.asarray(cts)[:e]})
    del out, sample, state, batch, compiled
    t0 = time.perf_counter()
    ref_ordered, ref = native.consensus(dag, n)
    print(f"bench: the reference took {time.perf_counter() - t0:.2f} s",
          file=sys.stderr)
    differing = [hashgraph.events_differing(ref, g, e) for g in got_all]
    got = got_all[-1]
    ordered = int(np.count_nonzero(got["rr"] >= 0))
    print(f"bench: {len(got_all)} of {steps} steps compared, the last "
          f"among them; events "
          f"differing {differing}; the last step by field "
          f"{hashgraph.mismatches(ref, got, e)}; ordered {ordered}, "
          f"the reference {ref_ordered}", file=sys.stderr)
    return {
        "attempted": steps,
        "failed": 0,
        # one number with a reading from the control (PERF.md section 2)
        "checks": {"events_differing": (max(differing), 0)},
        "readings": {"steps": steps, "steps_traced": steps_traced,
                     "events_per_step": e, "ordered": ordered},
        "internals": {"dag": dag, "n": n, "got": got, "reference": ref},
    }
