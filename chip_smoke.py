"""On-chip smoke: the main paths on a TPU, each checked against a reference.

    python chip_smoke.py             # one chip: batch consensus + served path
    python chip_smoke.py --chips 4   # four chips: p-sharded stream only

Phases (one chip):

1. **Batch consensus** at the BASELINE.json honest-DAG scale (1,024
   participants x 100,000 events) through the fused step that
   ``babble_tpu.cli sim`` runs, and the 64 x 65,536 DAG through the same
   step in ``walk`` ingest mode (the compiled Pallas kernel).  Round,
   witness, fame, round-received and consensus timestamp must equal the
   C++ reference (native/baseline_consensus.cpp) for every event.
2. **Served path** at the reference docker-testnet shape: 4 validators
   (heartbeat 10 ms, cache_size 50,000, tcp_timeout 200 ms), each built
   by ``cli.start_node`` as ``babble run`` builds it, on loopback TCP with
   in-memory app proxies, in one process (one process holds the chip).
   1,000 transactions (BASELINE.json configs[0]) must commit on every
   node in one order; node 0's event order must be a prefix of the
   order the Python oracle (consensus/oracle.py) finds in node 0's
   events.

With ``--chips 4``: the rolling-window stream at 4,096 participants,
its blocks spread over the 2x2 mesh ``make_mesh(4)`` builds, must order
exactly what the same stream orders on one chip (ordered set,
round-received, consensus timestamp).

Timings printed are smoke timings of one run, not benchmarks.  Any
failure ends the run with a nonzero exit and no result line; without a
TPU it exits before any phase.  The last stdout line on success is
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import socket
import sys
import tempfile
import threading
import time

SEED = 7

# phase 1: (participants, events, r_cap, ingest mode); BASELINE.json
# configs[2] and the live-fleet shape that fits the Pallas walk
BATCH_CASES = ((1024, 100_000, 16, "fast"), (64, 65_536, 512, "walk"))

# phase 2: reference docker testnet (README "Running it", SURVEY 2.5)
FLEET = dict(nodes=4, heartbeat_ms=10, cache_size=50_000, tcp_timeout_ms=200)
N_TXS = 1000
COMMIT_TIMEOUT_S = 600.0

# --chips 4: the rolling-window stream at 4,096 participants, the
# narrowest width on the int8 one-hot strongly-see path (ops/wide.py).
# A round takes about 64,000 events there and the first order comes at
# 256,000, so two batches of 128,000 fill the window.  At bench.run_10k's
# 10,000 participants (660,000 events, window 660,000) the one-chip run
# ordered 129,275 events in 131.9 s but the sharded run had not ordered
# when the call's 450 s ran out, and the chip budget of PR 21 was spent
# (PERF.md).
STREAM = dict(n=4096, events=256_000, window=256_000, batch_events=128_000,
              s_cap=110, r_cap=16, seq_window=48, compact_min=4096)
STREAM_CUT = ("4,096 participants (10,000 asked), 256,000 events of "
              "bench.run_10k's 1,000,000, window 256,000")


def say(*a) -> None:
    print(*a, flush=True)


def tpu_or_exit():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devs[0].platform}); "
              "nothing was run", file=sys.stderr)
        sys.exit(2)
    return devs


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ----------------------------------------------------------------------
# phase 1: batch consensus vs the C++ reference

def engine_fame(out, e: int):
    """Per-event fame in the reference's encoding (-1 not a witness,
    0 undecided, 1 famous, 2 not famous) from the [R, N] wslot/famous
    table."""
    import numpy as np

    wslot = np.asarray(out.wslot)
    famous = np.asarray(out.famous)
    fame = np.full(e, -1, np.int8)
    has = (wslot >= 0) & (wslot < e)
    fame[wslot[has]] = famous[has]
    return fame


def reference_mismatches(ref: dict, out, e: int) -> dict:
    """Per-field count of events whose value differs from the C++
    reference; cts is compared where the reference received the event."""
    import numpy as np

    rr = np.asarray(out.rr)[:e]
    recv = ref["rr"] >= 0
    got = {
        "round": np.asarray(out.round)[:e],
        "witness": np.asarray(out.witness)[:e],
        "fame": engine_fame(out, e),
        "rr": rr,
    }
    bad = {k: int(np.count_nonzero(ref[k] != v)) for k, v in got.items()}
    bad["cts"] = int(np.count_nonzero(
        ref["cts"][recv] != np.asarray(out.cts)[:e][recv]))
    return bad


def batch_phase(n: int, e: int, r_cap: int, mode: str, dev) -> dict:
    import jax
    import numpy as np

    from babble_tpu.cli import sim_step
    from babble_tpu.native import baseline_consensus, load_baseline
    from babble_tpu.ops.pallas_ingest import walk_supported
    from babble_tpu.ops.state import init_state
    from babble_tpu.sim.arrays import batch_from_arrays, random_gossip_arrays

    tag = f"batch {n}x{e} {mode}"
    if load_baseline() is None:
        raise RuntimeError("the C++ reference did not build (g++)")
    dag = random_gossip_arrays(n, e, seed=SEED)
    cfg, step = sim_step(dag, r_cap, mode)
    if mode == "walk" and not walk_supported(cfg.n, cfg.e_cap, cfg.s_cap):
        raise RuntimeError(f"{tag}: walk unsupported at {cfg}")

    ref_box: dict = {}

    def run_reference():
        t0 = time.perf_counter()
        try:
            ref_box["out"] = baseline_consensus(dag)
        except Exception as exc:   # re-raised on the main thread
            ref_box["err"] = exc
        ref_box["s"] = time.perf_counter() - t0

    # the reference runs on the host while the chip compiles and runs
    ref_thread = threading.Thread(target=run_reference)
    ref_thread.start()
    try:
        batch = batch_from_arrays(dag)
        t0 = time.perf_counter()
        compiled = step.lower(init_state(cfg), batch).compile()
        compile_s = time.perf_counter() - t0
        kernel = "tpu_custom_call" in compiled.as_text()
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(init_state(cfg), batch))
        first_s = time.perf_counter() - t0
        state = jax.block_until_ready(init_state(cfg))
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(state, batch))
        run_s = time.perf_counter() - t0
    finally:
        ref_thread.join()
    if "err" in ref_box:
        raise ref_box["err"]
    if ref_box.get("out") is None:
        raise RuntimeError(f"{tag}: the C++ reference returned no result")
    ref_ordered, ref = ref_box["out"]

    ordered = int(np.count_nonzero(np.asarray(out.rr)[:e] >= 0))
    bad = reference_mismatches(ref, out, e)
    facts = {
        "phase": tag, "participants": n, "events": e,
        "s_cap": cfg.s_cap, "r_cap": cfg.r_cap,
        "max_round": int(out.max_round), "lcr": int(out.lcr),
        "ordered": ordered, "reference_ordered": ref_ordered,
        "mismatches": bad, "pallas_kernel_compiled": kernel,
        "compile_s": compile_s, "first_run_s": first_s, "run_s": run_s,
        "reference_s": ref_box["s"], "peak_bytes_in_use": peak_bytes(dev),
    }
    say("smoke timing (one run, not a benchmark):", json.dumps(facts))
    if any(bad.values()) or ordered != ref_ordered:
        raise AssertionError(f"{tag}: differs from the C++ reference: {bad}")
    if ordered == 0 or int(out.max_round) >= cfg.r_cap - 1:
        raise AssertionError(f"{tag}: ordered {ordered}, max_round "
                             f"{int(out.max_round)} of r_cap {cfg.r_cap}")
    if mode == "walk" and not kernel:
        raise AssertionError(f"{tag}: no compiled Pallas kernel in the step")
    return facts


# ----------------------------------------------------------------------
# phase 2: 4 validators through the served path vs the oracle

def free_port_base(count: int, start: int = 31000) -> int:
    """First base port with ``count`` consecutive bindable loopback
    ports."""
    for base in range(start, 60000, count):
        socks = []
        try:
            for p in range(base, base + count):
                s = socket.socket()
                socks.append(s)
                s.bind(("127.0.0.1", p))
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free loopback port range")


def oracle_order(hg) -> list:
    """Replay an engine's inserted events, in slot order, through the
    reference-faithful Python oracle and return its consensus order."""
    from babble_tpu.consensus.oracle import OracleHashgraph
    from babble_tpu.store.inmem import InmemStore

    dag = hg.dag
    if dag.slot_base != 0:
        raise AssertionError(f"events evicted below slot {dag.slot_base}; "
                             "the oracle replay needs the whole DAG")
    oracle = OracleHashgraph(
        participants=dict(hg.participants),
        store=InmemStore(dict(hg.participants), FLEET["cache_size"]),
        verify_signatures=False,
    )
    for slot in range(dag.n_events):
        oracle.insert_event(dag.events[slot].clone())
    oracle.run_consensus()
    return oracle.consensus_events()


async def served_phase(base_dir: str, dev, n_txs: int = N_TXS) -> dict:
    import numpy as np

    from babble_tpu import cli, testnet

    n_nodes = FLEET["nodes"]
    base = free_port_base(4 * n_nodes)
    ports = testnet.PortLayout(gossip=base, submit=base + n_nodes,
                               commit=base + 2 * n_nodes,
                               service=base + 3 * n_nodes)
    datadirs = testnet.build_conf(base_dir, n_nodes, ports)
    parser = cli.build_parser()
    t0 = time.perf_counter()
    started = []
    try:
        for i, d in enumerate(datadirs):
            args = parser.parse_args([
                "run", "--datadir", d, "--no_client",
                "--node_addr", ports.of(i)["gossip"],
                "--service_addr", ports.of(i)["service"],
                "--heartbeat", str(FLEET["heartbeat_ms"]),
                "--cache_size", str(FLEET["cache_size"]),
                "--tcp_timeout", str(FLEET["tcp_timeout_ms"]),
                "--log_level", "warning",
            ])
            started.append(await cli.start_node(args))
        nodes = [node for node, _ in started]
        boot_s = time.perf_counter() - t0
        for node in nodes:
            node.run_task(gossip=True)

        rng = np.random.default_rng(SEED)
        txs = [b"smoke-%d-" % k + rng.bytes(16) for k in range(n_txs)]
        t0 = time.perf_counter()
        for k, tx in enumerate(txs):
            await nodes[k % n_nodes].proxy.submit_tx(tx)
        want = set(txs)
        deadline = time.monotonic() + COMMIT_TIMEOUT_S
        while True:
            for i, node in enumerate(nodes):
                if node.consensus_error is not None:
                    raise RuntimeError(
                        f"node {i} consensus failed") from node.consensus_error
            if all(want <= set(n.proxy.committed_transactions())
                   for n in nodes):
                break
            if time.monotonic() > deadline:
                counts = [len(n.proxy.committed_transactions())
                          for n in nodes]
                raise TimeoutError(f"committed {counts} of {n_txs} after "
                                   f"{COMMIT_TIMEOUT_S:.0f}s")
            await asyncio.sleep(0.05)
        commit_s = time.perf_counter() - t0
    finally:
        for node, service in started:
            await service.close()
            await node.shutdown()

    commits = [n.proxy.committed_transactions() for n in nodes]
    if any(c != commits[0] for c in commits) or sorted(commits[0]) != \
            sorted(txs):
        raise AssertionError("validators committed different sequences: "
                             f"{[len(c) for c in commits]}")
    orders = [n.core.hg.consensus_events() for n in nodes]
    k = min(len(o) for o in orders)
    if any(o[:k] != orders[0][:k] for o in orders):
        raise AssertionError("validators ordered events differently")
    hg = nodes[0].core.hg
    t0 = time.perf_counter()
    ref = oracle_order(hg)
    oracle_s = time.perf_counter() - t0
    # the node decides a round only once every chain's head has passed
    # it (the live finality gate); the oracle decides what the whole DAG
    # allows, so node 0's order must be a prefix of the oracle's
    mine = hg.consensus_events()
    if mine != ref[:len(mine)]:
        raise AssertionError(f"node 0 order ({len(mine)} events) is not a "
                             f"prefix of the oracle's ({len(ref)})")
    device = hg.state.la.devices().pop()
    facts = {
        "phase": "served", **FLEET,
        "txs": n_txs, "events": hg.dag.n_events, "ordered_events": len(mine),
        "oracle_ordered_events": len(ref),
        "engine_device": f"{device.platform}:{device.id}",
        "boot_s": boot_s, "submit_to_all_committed_s": commit_s,
        "oracle_s": oracle_s, "peak_bytes_in_use": peak_bytes(dev),
    }
    say("smoke timing (one run, not a benchmark):", json.dumps(facts))
    if device.platform != dev.platform:
        raise AssertionError(f"engine state on {device}, not the chip")
    return facts


# ----------------------------------------------------------------------
# --chips 4: p-sharded rolling-window stream vs the same stream on one chip

def stream_phase(devs, n: int, events: int, window: int,
                 batch_events: int, s_cap: int, r_cap: int,
                 seq_window: int, compact_min: int) -> dict:
    import gc

    import jax

    from babble_tpu.ops.state import DagConfig
    from babble_tpu.ops.stream import stream_consensus
    from babble_tpu.ops.wide import block_count
    from babble_tpu.parallel import make_mesh
    from babble_tpu.sim.arrays import random_gossip_arrays

    t0 = time.perf_counter()
    dag = random_gossip_arrays(n, events, seed=SEED)
    dag_s = time.perf_counter() - t0
    cfg = DagConfig(n=n, e_cap=window, s_cap=s_cap, r_cap=r_cap, coord8=True)
    # the mesh users get (2x2 on four chips); the stream spreads its
    # blocks over every device of it (ops/wide.block_sharding)
    mesh = make_mesh(len(devs), devices=devs)
    blocks = -(-block_count(cfg) // mesh.size) * mesh.size
    kw = dict(batch_events=batch_events, n_blocks=blocks, round_margin=0,
              seq_window=seq_window, compact_min=compact_min,
              log=lambda *a: print(*a, file=sys.stderr, flush=True))
    facts = {"phase": f"stream {n}x{events}", "participants": n,
             "events": events, "window": window, "blocks": blocks,
             "mesh": dict(mesh.shape), "dag_build_s": dag_s}
    runs = {}
    # one chip first: at 10k its window fills most of device 0, which
    # the sharded run's leftovers did not leave it (PR 21)
    for tag, m in (("one_chip", None), ("sharded", mesh)):
        t0 = time.perf_counter()
        stream = stream_consensus(cfg, dag, mesh=m, **kw)
        facts[f"{tag}_s"] = time.perf_counter() - t0
        runs[tag] = (stream.ordered, stream.ordered_total, stream.lcr)
        say(f"stream {tag}: {stream.ordered_total} ordered, lcr "
            f"{stream.lcr}, {facts[f'{tag}_s']:.1f} s")
        if m is not None:
            shards = stream.la_blocks.addressable_shards
            shard_devs = {s.device for s in shards}
            facts.update(
                la_sharding=str(stream.la_blocks.sharding),
                la_devices=sorted(d.id for d in shard_devs),
                la_shard_shape=list(shards[0].data.shape),
                state_devices=sorted(d.id for d in stream.state.rr.devices()),
                evicted=stream.evicted,
            )
        # the next run needs the memory back, from the blocks and from
        # the loaded programs
        del stream
        jax.clear_caches()
        gc.collect()
    (sh, sh_total, sh_lcr), (one, one_total, one_lcr) = (
        runs["sharded"], runs["one_chip"])
    facts.update(ordered=sh_total, lcr=sh_lcr,
                 peak_bytes_in_use=[peak_bytes(d) for d in devs])
    say("smoke timing (one run, not a benchmark):", json.dumps(facts))
    if shard_devs != set(devs) or \
            facts["la_shard_shape"][0] != blocks // len(devs):
        raise AssertionError(
            f"blocks live on {facts['la_devices']} as "
            f"{facts['la_shard_shape']}, not {blocks // len(devs)} of "
            f"{blocks} on each of {len(devs)} chips")
    if sh_total == 0:
        raise AssertionError("the stream ordered nothing")
    if sh != one or sh_lcr != one_lcr:
        raise AssertionError(
            f"sharded stream ({sh_total} ordered, lcr {sh_lcr}) differs "
            f"from one chip ({one_total}, lcr {one_lcr})")
    return facts


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = tpu_or_exit()
    if len(devs) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devs)} found",
              file=sys.stderr)
        return 2
    import os

    from babble_tpu.ops import aot

    cache_dir = aot.configure()
    if not cache_dir:
        raise RuntimeError("no compile-cache directory")
    cached_before = len(os.listdir(cache_dir))
    devs = devs[:args.chips]
    dev = devs[0]
    say(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")
    if args.chips == 4:
        say(f"cut: {STREAM_CUT}")
        stream_phase(devs, **STREAM)
    else:
        for case in BATCH_CASES:
            batch_phase(*case, dev)
        with tempfile.TemporaryDirectory() as base_dir:
            asyncio.run(served_phase(base_dir, dev))
    say("compile cache:", json.dumps({
        "dir": cache_dir, "entries_before": cached_before,
        "entries_after": len(os.listdir(cache_dir)),
        **aot.compile_counts()}))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
