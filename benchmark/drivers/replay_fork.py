"""Driver ``replay_fork``: a recorded DAG in which validators equivocate,
ordered whole by the batch path (``cli.sim_step``, which runs the
fork-aware pipeline for a DAG that holds an equivocation), step after
step from the same inputs, for the window.

The run's seed draws the DAG from the benchmark's own generator
(``reference/fork_dag.cpp``), which gives every seed the same sizes: every
validator mints as many events, the forkers fork at the same index, so
the longest chain (``s_cap``) is the same.  The program's level schedule
is padded to the configuration's ``sched_rows`` x branch columns, so
every seed runs the one compiled program on the same shapes.  After the
window, a sample of the window's steps drawn from the seed, the last
step among them, is compared event by event with the fork-aware plain
reference (``reference/fork_consensus.cpp``).

With ``--trace 1`` the driver reads the device time by scope path
(``benchmark/scopes.py``) into ``readings["scopes"]`` itself, while the
trace still exists."""

from __future__ import annotations

import sys
import time

#: steps of the window whose output is compared with the reference
SAMPLE = 8


def run(ctx) -> dict:
    import jax
    import numpy as np

    from babble_tpu.cli import sim_inputs, sim_step
    from babble_tpu.sim.arrays import ArrayDag

    from benchmark import scopes
    from benchmark.reference import fork_native, hashgraph

    conf = ctx.config
    n, e = conf["participants"], conf["events"]
    dag = fork_native.fork_dag(n, e, ctx.seed, conf["byzantine"])
    adag = ArrayDag(n, dag["sp"], dag["op"], dag["creator"], dag["seq"],
                    dag["ts"], dag["mbit"], dag["levels"], ctx.seed)
    cfg, step = sim_step(adag, conf["r_cap"])
    want = (conf["branch_slots"], conf["s_cap"])
    if (getattr(cfg, "k", None), cfg.s_cap) != want:
        raise ValueError(f"the step's (branch slots, s_cap) "
                         f"{(getattr(cfg, 'k', None), cfg.s_cap)} are not "
                         f"the configuration's {want}")
    inputs = sim_inputs(adag, cfg, conf["sched_rows"])
    compiled = step.lower(*inputs).compile()
    jax.block_until_ready(compiled(*inputs))          # warm step

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
            out = jax.block_until_ready(compiled(*inputs))
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
    readings = {"steps": steps, "steps_traced": steps_traced,
                "events_per_step": e,
                "closure_steps": int(out.closure_steps),
                "vote_steps": int(out.vote_steps),
                "max_round": int(out.max_round)}
    if ctx.trace_dir:
        readings["scopes"] = scopes.reduce_scopes(
            ctx.trace_dir, scopes.scope_map(compiled.as_text()))
        paths = readings["scopes"]["scopes"]
        print(f"bench: device ms per traced step by scope path "
              f"{ {p: round(1000 * v / steps_traced, 3) for p, v in paths.items()} }; "
              f"their sum {sum(paths.values()):.6f} s, the operations' "
              f"union {readings['scopes']['ops_busy_s']:.6f} s",
              file=sys.stderr)

    got_all = []
    for rnd, wit, wslot, famous, rr, cts in sample:
        got_all.append({
            "round": np.asarray(rnd)[:e], "witness": np.asarray(wit)[:e],
            "fame": hashgraph.fame_per_event(
                np.asarray(wslot), np.asarray(famous), e),
            "rr": np.asarray(rr)[:e], "cts": np.asarray(cts)[:e]})
    del out, sample, inputs, compiled
    t0 = time.perf_counter()
    ref_ordered, ref = fork_native.consensus(dag, n)
    print(f"bench: the reference took {time.perf_counter() - t0:.2f} s",
          file=sys.stderr)
    differing = [hashgraph.events_differing(ref, g, e) for g in got_all]
    got = got_all[-1]
    ordered = int(np.count_nonzero(got["rr"] >= 0))
    readings["ordered"] = ordered
    print(f"bench: {len(got_all)} of {steps} steps compared, the last "
          f"among them; events differing {differing}; the last step by "
          f"field {hashgraph.mismatches(ref, got, e)}; ordered {ordered}, "
          f"the reference {ref_ordered}; max_round "
          f"{readings['max_round']} of r_cap {cfg.r_cap}; closure steps "
          f"{readings['closure_steps']}, vote steps "
          f"{readings['vote_steps']} a step", file=sys.stderr)
    return {
        "attempted": steps,
        "failed": 0,
        # one number, with readings from two controls (PERF.md section 2)
        "checks": {"events_differing": (max(differing), 0)},
        "readings": readings,
        "internals": {"dag": dag, "n": n, "got": got, "reference": ref},
    }


def control_readings(internals: dict) -> dict:
    """The controls' numbers, each compared with the reference as the
    run compares the program: the reference with mean consensus
    timestamps (``ts_rule`` 1) and the fork-blind reference (see is
    plain ancestry), each on the run's DAG in the program's place."""
    from benchmark.reference import fork_native, hashgraph

    dag, n, ref = internals["dag"], internals["n"], internals["reference"]
    e = len(dag["sp"])
    _, mean = fork_native.consensus(dag, n, ts_rule=1)
    _, blind = fork_native.consensus(dag, n, fork_blind=True)
    return {"events_differing.ts_rule_1":
            hashgraph.events_differing(ref, mean, e),
            "events_differing.fork_blind":
            hashgraph.events_differing(ref, blind, e)}
