"""CLI (reference cmd/main.go:39-260): keygen, run, sim.

- ``keygen``  — print (or write to a datadir) a PEM keypair.
- ``run``     — boot a node: key + peers from the datadir, TCP transport,
  socket or inmem proxy, /Stats service, then the gossip loop.
- ``sim``     — generate a random gossip DAG and run batch consensus on
  the device pipeline (no networking; the benchmark path).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time


def cmd_keygen(args) -> int:
    from .crypto.keys import PemKeyFile, generate_key, pem_dump

    key = generate_key()
    if args.datadir:
        pem = PemKeyFile(args.datadir)
        if pem.exists():
            print(f"key already exists in {args.datadir}", file=sys.stderr)
            return 1
        pem.write(key)
        print(f"wrote {pem.path}")
    priv, pub = pem_dump(key)
    print(f"PublicKey:\n{pub}")
    if not args.datadir:
        print(f"PrivateKey:\n{priv}")
    return 0


def _parse_fork_caps(spec: str, flag: str = "--fork_caps"):
    """'e,s,r' -> (e, s, r), failing at the flag instead of as a bare
    IndexError inside the consensus loop."""
    if not spec:
        return None
    parts = spec.split(",")
    if len(parts) != 3:
        raise SystemExit(
            f"{flag} wants exactly 'e,s,r' (got {spec!r})"
        )
    try:
        caps = tuple(int(x) for x in parts)
    except ValueError:
        raise SystemExit(f"{flag} values must be integers: {spec!r}")
    if any(v <= 0 for v in caps):
        raise SystemExit(f"{flag} values must be positive: {spec!r}")
    return caps


async def start_node(args):
    """Build and start one node as ``babble run`` does: engine (or its
    checkpoint), TCP transport, app proxy, /Stats service.  Returns
    ``(node, service)``; the caller runs ``node.run`` and shuts both
    down."""
    import os

    # Persistent jit cache, shared by every node on the host: live
    # gossip produces a spread of bucketed batch shapes, and without the
    # cache each (kpad, tpad, bpad) combination costs a fresh multi-
    # second XLA compile on every node, every run — a compile storm that
    # dominates fleet throughput.
    cache_dir = ""
    if args.jax_cache != "off":
        from .ops import aot

        # one surface for the cache flags (ops/aot.py): persistent XLA
        # cache + compile-event listeners; the AOT shape manifest lives
        # in the same directory and Node prewarms from it at boot
        cache_dir = aot.configure(
            args.jax_cache or None,
            fallback=os.path.join(args.datadir, "jax_cache"))

    from .crypto.keys import PemKeyFile
    from .net.peers import JSONPeers
    from .net.tcp_transport import new_tcp_transport
    from .node.config import Config
    from .node.node import Node
    from .proxy.inmem import InmemAppProxy
    from .proxy.socket_app import SocketAppProxy
    from .service.service import Service

    key = PemKeyFile(args.datadir).read()
    peers = JSONPeers(args.datadir).peers()
    # membership plane: a JOINER's epoch-0 validator set is the
    # founders' peers.json, while its own address rides only the
    # gossip book — it observes until its signed join tx commits
    bootstrap_peers = None
    bp_path = getattr(args, "bootstrap_peers", "")
    if bp_path:
        from .net.peers import peers_from_file

        bootstrap_peers = peers_from_file(bp_path)

    engine = None
    ckpt_dir = getattr(args, "checkpoint_dir", "")
    if ckpt_dir and os.path.isdir(ckpt_dir):
        # corruption-tolerant restart: a rotten checkpoint degrades to
        # a fresh engine + WAL replay + gossip/fast-forward instead of
        # a dead node (the chaos plane's disk-rot scenario pins this)
        from .store import load_checkpoint_tolerant

        engine, ckpt_err = load_checkpoint_tolerant(ckpt_dir)
        if ckpt_err is not None:
            if not getattr(args, "wal_dir", ""):
                # without a WAL there is no mint floor and no seq
                # probe: booting a fresh root here would re-mint every
                # published seq and peers would read this identity as
                # an equivocator (the crash-recovery-amnesia defect) —
                # refuse instead of silently poisoning the fleet
                raise SystemExit(
                    f"checkpoint {ckpt_dir} is unreadable ({ckpt_err}) "
                    "and no --wal_dir is configured: a fresh boot would "
                    "re-mint published sequence numbers.  Configure "
                    "--wal_dir (recovery degrades safely through the "
                    "WAL + seq probe), restore the checkpoint, or "
                    "remove the directory to explicitly start over."
                )
            print(
                f"warning: checkpoint {ckpt_dir} unreadable ({ckpt_err}); "
                "starting fresh and recovering from the WAL",
                file=sys.stderr,
            )
    if engine is not None:
        from .store.checkpoint import engine_mode

        mode = engine_mode(engine)
        want = ("byzantine" if args.byzantine
                else getattr(args, "engine", "fused"))
        if mode != want:
            raise SystemExit(
                f"checkpoint {ckpt_dir} engine kind '{mode}' does not "
                f"match the configured engine '{want}'"
            )
        if mode == "byzantine":
            caps = _parse_fork_caps(getattr(args, "fork_caps", ""))
            if caps:
                # the checkpoint carries no capacity hints: re-apply the
                # pre-sizing or every resume pays the growth re-jits
                engine.pre_size(caps)
        elif mode == "wide":
            want_caps = _parse_fork_caps(getattr(args, "wide_caps", ""),
                                         flag="--wide_caps")
            have = (engine.cfg.e_cap, engine.cfg.s_cap, engine.cfg.r_cap)
            if want_caps and tuple(want_caps) != have:
                # wide capacities are fixed at boot; the snapshot's
                # shapes win on resume — say so instead of letting the
                # operator believe the flag took effect
                print(
                    f"warning: --wide_caps {want_caps} ignored — the "
                    f"resumed checkpoint's window capacities are {have} "
                    "and cannot change post-boot",
                    file=sys.stderr,
                )
        n_ev = (len(engine.dag.events) if mode == "byzantine"
                else engine.dag.n_events)
        print(f"resumed from checkpoint {ckpt_dir}: "
              f"{n_ev} events, "
              f"{engine.consensus_events_count()} in consensus order")

    conf = Config(
        heartbeat=args.heartbeat / 1000.0,
        tcp_timeout=args.tcp_timeout / 1000.0,
        cache_size=args.cache_size,
        consensus_interval=args.consensus_interval / 1000.0,
        pipeline=not getattr(args, "no_pipeline", False),
        gossip_fanout=getattr(args, "gossip_fanout", 1),
        gossip_inflight=getattr(args, "gossip_inflight", 4),
        gossip_eager=not getattr(args, "no_eager_gossip", False),
        coalesce_max=getattr(args, "coalesce_max", 1024),
        coalesce_latency=getattr(args, "coalesce_latency", 50) / 1000.0,
        mint_backpressure=getattr(args, "mint_backpressure", 0) or None,
        seq_window=args.seq_window or None,
        # 0 disables the inactivity policy (a silent peer then pins
        # eviction fleet-wide, the pre-PR-8 behavior); -1 = default
        inactive_rounds=(
            None if getattr(args, "inactive_rounds", -1) == 0
            else (getattr(args, "inactive_rounds", -1)
                  if getattr(args, "inactive_rounds", -1) > 0 else 32)
        ),
        ff_verify=not getattr(args, "no_ff_verify", False),
        anchor_interval=getattr(args, "anchor_interval", 2048),
        bootstrap_peers=bootstrap_peers,
        byzantine=args.byzantine,
        fork_k=args.fork_k,
        fork_caps=_parse_fork_caps(getattr(args, "fork_caps", "")),
        engine=getattr(args, "engine", "fused"),
        wide_caps=_parse_fork_caps(getattr(args, "wide_caps", ""),
                                   flag="--wide_caps"),
        wal_dir=getattr(args, "wal_dir", ""),
        wal_fsync=getattr(args, "wal_fsync", "batch"),
        kernel_class=getattr(args, "kernel_class", "auto"),
        # kernel working-set diet (ROADMAP item 4): both pins are
        # bit-parity-preserving — they select kernel math, not
        # semantics (bench.py diet runs the before/after arms)
        packed_votes=not getattr(args, "no_packed_votes", False),
        frontier=not getattr(args, "no_frontier", False),
        # AOT prewarm shares the jit-cache root: the shape manifest
        # sits beside the persistent XLA cache it replays into
        aot_dir=(
            "" if getattr(args, "no_aot_prewarm", False) else cache_dir
        ),
        # attribution plane (ISSUE 11)
        lineage=not getattr(args, "no_lineage", False),
        flight=not getattr(args, "no_flight", False),
        phase_probe=getattr(args, "phase_probe", False),
        commit_slo=getattr(args, "commit_slo", 1000) / 1000.0,
    )
    conf.logger.setLevel(args.log_level.upper())

    transport = await new_tcp_transport(
        args.node_addr, max_pool=args.max_pool,
        timeout=conf.tcp_timeout,
    )
    if getattr(args, "chaos_plan", ""):
        # self-injected faults for live fleets: every node wraps its TCP
        # transport in the same (plan, seed)-driven FaultyTransport the
        # in-memory scenario runner uses, deriving its own link identity
        # from the canonical peer order — no per-node flags needed
        transport = _chaos_wrap(transport, args, key, peers)
        print(f"chaos plan {args.chaos_plan} active "
              f"(seed {transport.injector.seed})", file=sys.stderr)

    if args.no_client:
        proxy = InmemAppProxy()
    else:
        proxy = SocketAppProxy(
            args.client_addr, args.proxy_addr,
            timeout=conf.tcp_timeout,
            submit_per_client=getattr(args, "submit_per_client", 1024),
            submit_total=getattr(args, "submit_total", 8192),
            submit_adaptive=getattr(args, "submit_adaptive", False),
        )
        await proxy.start()

    node = Node(conf, key, peers, transport, proxy, engine=engine)
    if engine is None:
        # Node.init is recovery-aware: it skips the root mint when WAL
        # replay already restored a head, and defers it while the seq
        # probe negotiates a skip-ahead with the fleet
        node.init()
    service = Service(args.service_addr, node,
                      allow_remote_debug=args.allow_remote_debug)
    await service.start()
    print(f"node {node.core.id} listening on {transport.local_addr()}, "
          f"stats on http://{service.bind_addr}/Stats, "
          f"metrics on http://{service.bind_addr}/metrics")
    return node, service


async def _run_node(args) -> int:
    # _chaos_wrap (inside start_node) reads the wall clock BY DESIGN:
    # live fleets map plan ticks onto shared wall time (--chaos_epoch)
    # so restarted nodes rejoin the fault schedule in phase — the wall
    # clock drives only the injector's tick cursor, never event bodies
    # (those go through Core.now_ns)
    node, service = await start_node(args)  # babble-lint: disable=consensus-nondeterminism
    ckpt_dir = getattr(args, "checkpoint_dir", "")
    saver = None
    if ckpt_dir:
        saver = asyncio.create_task(
            _checkpoint_loop(node, ckpt_dir, args.checkpoint_interval)
        )
    try:
        await node.run(gossip=True)
    except Exception:
        # crash post-mortem (ISSUE 11): an unhandled select-loop error
        # dumps the flight recorder's last-N-transitions narrative next
        # to the datadir before the process dies — the in-memory ring
        # would otherwise die with it
        _dump_flight_on_crash(node, args.datadir)
        raise
    finally:
        if saver is not None:
            saver.cancel()
        if ckpt_dir:
            await node.save_checkpoint(ckpt_dir)
        await service.close()
        await node.shutdown()
    return 0


def _dump_flight_on_crash(node, datadir: str) -> None:
    import os

    try:
        path = os.path.join(datadir, "flight-crash.json")
        with open(path, "w") as f:
            json.dump({"stats": node.flight.stats(),
                       "records": node.flight.dump()}, f, indent=1)
        print(f"flight recorder dumped to {path}", file=sys.stderr)
    except Exception as e:   # the dump must never mask the real crash
        print(f"flight dump failed: {e}", file=sys.stderr)


def _chaos_wrap(transport, args, key, peers):
    """Wrap a live node's transport in a FaultyTransport driven by the
    scenario (or bare fault-plan) JSON at --chaos_plan.  Ticks map to
    wall time through the scenario's tick_seconds; link identities are
    canonical participant ids, so every node in the fleet derives the
    same per-link fault streams from the shared seed."""
    import time

    from .chaos import FaultInjector, FaultPlan, FaultyTransport, Scenario
    from .net.peers import canonical_ids

    with open(args.chaos_plan) as f:
        spec = json.load(f)
    joiners = 0
    if "plan" in spec:
        sc = Scenario.from_dict(spec)
        plan, tick_seconds, seed = sc.plan, sc.tick_seconds, sc.seed
        joiners = sc.joiners
    else:
        plan, tick_seconds, seed = FaultPlan.from_dict(spec), 0.05, 0
    if getattr(args, "chaos_seed", None) is not None:
        seed = args.chaos_seed
    # Link identities: the fleet DRIVER's address map when provided
    # (--chaos_addrs, written by chaos run --live next to the scenario
    # JSON) — the only exact source once joiners exist, because it
    # names every scheduled joiner's address/index BEFORE the joiner's
    # transition commits, so founders apply link faults on
    # founder->joiner traffic too and multiple joiners cannot collide
    # on one index.  Without it, fall back to canonical ids over the
    # FOUNDING set (a joiner's peers.json carries its own address too,
    # and folding that key into the sort would renumber every
    # founder's per-link fault stream); extra address-book entries
    # take the joiner indices in address order — exact only for a
    # single joiner, so hand-rolled multi-joiner fleets should pass
    # --chaos_addrs.
    addrs_path = getattr(args, "chaos_addrs", "")
    bp_path = getattr(args, "bootstrap_peers", "")
    if bp_path:
        from .net.peers import peers_from_file

        founders = peers_from_file(bp_path)
    else:
        founders = peers
    if addrs_path:
        with open(addrs_path) as f:
            addr_index = {a: int(i) for a, i in json.load(f).items()}
        own = addr_index[transport.local_addr()]
    else:
        ids = canonical_ids(founders)
        addr_index = {p.net_addr: ids[p.pub_key_hex] for p in founders}
        extra = sorted(
            p.net_addr for p in peers if p.pub_key_hex not in ids
        )
        for j, addr in enumerate(extra):
            addr_index[addr] = len(founders) + j
        own = (ids[key.pub_hex] if key.pub_hex in ids
               else addr_index[transport.local_addr()])
    plan.validate(len(founders), joiners=joiners)
    # tick 0 is the FLEET's epoch, not this process's boot: a node
    # relaunched mid-run (crash/restart schedule) must rejoin the shared
    # timeline, or it would replay the plan's partition/byzantine
    # schedule out of phase with everyone else.  The fleet driver passes
    # --chaos_epoch (unix seconds) to every node for exactly this;
    # without it, boot time is the epoch (single-boot fleets).
    epoch = getattr(args, "chaos_epoch", None)
    if epoch is None:
        epoch = time.time()
    injector = FaultInjector(
        plan, seed,
        clock=lambda: (time.time() - epoch) / tick_seconds,
        # the token bucket's refill clock: elapsed ticks x tick_seconds
        # must equal elapsed wall seconds, or bandwidth caps refill at
        # the wrong rate whenever the scenario stretches its timeline
        tick_seconds=tick_seconds,
    )
    return FaultyTransport(
        transport, injector, own, addr_index,
        # the forge_snapshot actor needs its own participant key to
        # re-sign the doctored proof — without it the mode would be a
        # silent no-op in live fleets
        forge_key=(key if injector.is_snapshot_forger(own) else None),
    )


async def _checkpoint_loop(node, ckpt_dir: str, interval: float) -> None:
    while True:
        await asyncio.sleep(interval)
        try:
            await node.save_checkpoint(ckpt_dir)
        except Exception as e:
            print(f"checkpoint failed: {e}", file=sys.stderr)


def cmd_run(args) -> int:
    try:
        return asyncio.run(_run_node(args))
    except KeyboardInterrupt:
        return 0


def sim_step(dag, r_cap: int, mode: str = "fast"):
    """The batch path of ``sim``: the capacities for an ArrayDag and the
    whole-DAG consensus step over them.  Returns ``(cfg, step)``;
    ``step(*sim_inputs(dag, cfg))`` runs it.

    A DAG that holds an equivocation (``dag.branch_slots`` above 1)
    runs the fork-aware pipeline (``ops.forks.fork_pipeline_impl``, as
    ``ForkHashgraph`` does live) with that many branch slots per
    creator; any other runs the fused ingest + fame + order step in
    ingest ``mode``."""
    import functools

    import jax

    s_cap = max(64, dag.max_chain + 1)
    if dag.branch_slots > 1:
        from .ops.forks import ForkConfig, fork_pipeline_impl

        cfg = ForkConfig(n=dag.n, k=dag.branch_slots, e_cap=dag.n_events,
                         s_cap=s_cap, r_cap=r_cap)
        return cfg, jax.jit(functools.partial(fork_pipeline_impl, cfg))

    from .ops.state import DagConfig
    from .parallel.sharded import consensus_step_impl

    cfg = DagConfig(n=dag.n, e_cap=dag.n_events, s_cap=s_cap, r_cap=r_cap)
    return cfg, jax.jit(functools.partial(consensus_step_impl, cfg, mode))


def sim_inputs(dag, cfg, sched_rows: int = 0) -> tuple:
    """The arguments of ``sim_step``'s step for ``dag``: a fresh state
    and the event batch, or the fork batch alone.  A ``sched_rows`` pads
    the level schedule to that many rows, each as wide as a level can be
    (one event per creator, or per branch column), so that DAGs of equal
    sizes run one compiled program."""
    from .ops.forks import ForkConfig
    from .sim.arrays import (
        batch_from_arrays, fork_batch_from_arrays, pad_schedule,
    )

    if isinstance(cfg, ForkConfig):
        return (fork_batch_from_arrays(dag, cfg, sched_rows),)
    import jax.numpy as jnp
    import numpy as np

    from .ops.state import init_state

    batch = batch_from_arrays(dag)
    sched = pad_schedule(np.asarray(batch.sched), sched_rows, dag.n)
    return init_state(cfg), batch._replace(sched=jnp.asarray(sched))


def cmd_sim(args) -> int:
    import jax
    import numpy as np

    from .sim.arrays import random_gossip_arrays

    t0 = time.perf_counter()
    dag = random_gossip_arrays(args.nodes, args.events, seed=args.seed)
    cfg, step = sim_step(dag, args.rounds)
    inputs = sim_inputs(dag, cfg)
    print(f"host build: {time.perf_counter()-t0:.2f}s "
          f"(native={__import__('babble_tpu.native', fromlist=['x']).available()})",
          file=sys.stderr)
    t0 = time.perf_counter()
    out = step(*inputs)
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    if args.profile:
        # xprof trace of the steady-state step (reference piggy-backs Go
        # pprof on its HTTP listener, cmd/main.go:26; the TPU equivalent
        # is a jax profiler trace viewable in tensorboard/xprof)
        with jax.profiler.trace(args.profile):
            out = step(*inputs)
            jax.block_until_ready(out)
        print(f"profile written to {args.profile}", file=sys.stderr)
    t0 = time.perf_counter()
    out = step(*inputs)
    jax.block_until_ready(out)
    run_s = time.perf_counter() - t0
    ordered = int(np.count_nonzero(np.asarray(out.rr)[: args.events] >= 0))
    print(json.dumps({
        "nodes": args.nodes,
        "events": args.events,
        "ordered": ordered,
        "last_consensus_round": int(out.lcr),
        "max_round": int(out.max_round),
        "compile_s": round(compile_s, 3),
        "run_s": round(run_s, 4),
        "events_per_sec": round(ordered / run_s, 1) if run_s > 0 else None,
    }))
    return 0


async def _run_dummy(args) -> int:
    from .proxy.dummy import DummySocketClient

    client = DummySocketClient(args.node_addr, args.listen, log_path=args.log)
    await client.start()
    if not args.quiet:
        print(f"dummy client: submit -> {args.node_addr}, "
              f"commits <- {client.proxy.bind_addr}; type messages:")

    loop = asyncio.get_running_loop()
    last_seen = 0

    async def print_commits():
        nonlocal last_seen
        while True:
            await asyncio.sleep(0.3)
            msgs = client.state.get_messages()
            for m in msgs[last_seen:]:
                print(f"<< {m}")
            last_seen = len(msgs)

    printer = None if args.quiet else asyncio.create_task(print_commits())
    try:
        if args.quiet:
            await asyncio.Event().wait()  # serve until killed
        else:
            while True:
                line = await loop.run_in_executor(None, sys.stdin.readline)
                if not line:
                    break
                line = line.strip()
                if line:
                    await client.submit_tx(line.encode())
    finally:
        if printer is not None:
            printer.cancel()
        await client.close()
    return 0


def cmd_dummy(args) -> int:
    """Interactive chat client (reference cmd/dummy_client/main.go)."""
    try:
        return asyncio.run(_run_dummy(args))
    except KeyboardInterrupt:
        return 0


def cmd_testnet(args) -> int:
    from . import testnet as tn

    ports = tn.PortLayout(
        gossip=args.base_port, submit=args.base_port + 1000,
        commit=args.base_port + 2000, service=args.base_port + 3000,
    )
    if args.testnet_cmd == "conf":
        dirs = tn.build_conf(args.dir, args.n, ports, overwrite=args.overwrite)
        print(f"wrote {len(dirs)} node configs under {args.dir}")
        return 0
    if args.testnet_cmd == "watch":
        while True:
            print("\x1b[2J\x1b[H" + tn.format_stats(
                tn.watch_once(args.n, ports)))
            if args.once:
                return 0
            time.sleep(args.interval)
    if args.testnet_cmd in ("health", "trace"):
        # the read-only observability sweeps share the fleet helpers:
        # a same-host testnet is just a HostLayout of explicit
        # host:service_port entries
        from . import fleet as fl

        layout = fl.HostLayout(
            [ports.of(i)["service"] for i in range(args.n)]
        )
        if args.testnet_cmd == "health":
            return _print_health(fl, layout, args.json)
        return _print_trace(fl, layout, args.txid, args.json)
    if args.testnet_cmd == "bombard":
        if getattr(args, "clients", 1) > 1:
            # many-client harness: per-connection admission identities,
            # structured-overloaded backoff, shed/error accounting
            counts = asyncio.run(tn.bombard_many(
                args.n, clients=args.clients, rate=args.rate,
                duration=args.duration, ports=ports,
                batch=getattr(args, "batch", 1)))
            print(f"submitted {counts['sent']} transactions "
                  f"({counts['shed']} shed, {counts['errors']} errors, "
                  f"{counts['clients']} clients)")
            return 0
        sent = asyncio.run(
            tn.bombard(args.n, args.rate, args.duration, ports))
        print(f"submitted {sent} transactions")
        return 0
    if args.testnet_cmd == "run":
        runner = tn.TestnetRunner(
            args.dir, args.n, heartbeat_ms=args.heartbeat,
            with_clients=not args.no_clients, ports=ports,
        )
        runner.start()
        print(f"{args.n} nodes up; /Stats at "
              f"http://127.0.0.1:{args.base_port + 3000}..{args.base_port + 3000 + args.n - 1}"
              f"; ctrl-C to stop")
        try:
            while True:
                time.sleep(args.interval)
                print(tn.format_stats(tn.watch_once(args.n, ports)))
        except KeyboardInterrupt:
            pass
        finally:
            runner.stop()
        return 0
    raise SystemExit(f"unknown testnet subcommand {args.testnet_cmd}")


def _print_health(fl, layout, as_json: bool) -> int:
    """One /healthz sweep rendered as the fleet table (or JSON).  Exit
    1 when any node is unreachable, degraded, or the fleet diverges —
    a health verb that always exits 0 is a decoration."""
    rows = fl.health_hosts(layout)
    divergence = fl.health_divergence(rows)
    if as_json:
        print(json.dumps({"nodes": rows, "divergence": divergence},
                         indent=1))
    else:
        print(fl.format_health(rows, divergence))
    ok = (
        all("health" in r for r in rows)
        and all(r["health"].get("status") == "ok" for r in rows)
        and not any(d["severity"] == "error" for d in divergence)
    )
    return 0 if ok else 1


def _print_trace(fl, layout, txid: str, as_json: bool) -> int:
    """Stitch one tx's cross-node lineage; exit 1 when nothing was
    found (wrong txid, lineage disabled, or the ledgers rolled off)."""
    from .obs.lineage import format_trace

    st = fl.trace_tx(layout, txid)
    if as_json:
        print(json.dumps(st, indent=1))
    else:
        if st["errors"]:
            for e in st["errors"]:
                print(f"{e['host']}: {e['kind']}: {e['error']}",
                      file=sys.stderr)
        print(format_trace(st))
    return 0 if st["timeline"] else 1


def cmd_fleet(args) -> int:
    from . import fleet as fl
    from . import testnet as tn

    with open(args.hosts) as f:
        hosts = [ln.strip() for ln in f if ln.strip()]
    if not hosts:
        raise SystemExit(f"{args.hosts} lists no hosts")
    if getattr(args, "rate", 1.0) <= 0:
        raise SystemExit("--rate must be positive")
    layout = fl.HostLayout(
        hosts, gossip_port=args.gossip_port, submit_port=args.submit_port,
        commit_port=args.commit_port, service_port=args.service_port,
    )
    if (layout.explicit_service_ports()
            and args.fleet_cmd not in ("watch", "scrape", "trace",
                                       "health")):
        # 'host:port' entries name SERVICE endpoints; conf/bombard
        # would resolve every node to one shared default gossip/submit
        # port on the same host and silently misroute
        raise SystemExit(
            "host:port entries are only valid for the read-only "
            f"sweeps (watch/scrape/trace/health), not '{args.fleet_cmd}'"
            " — list bare hosts and use the port flags instead"
        )
    if args.fleet_cmd == "conf":
        dirs = fl.build_fleet_conf(
            __import__("os").path.join(args.dir, "conf"), layout
        )
        scripts = fl.write_deploy_scripts(args.dir, layout)
        print(f"wrote {len(dirs)} node configs + "
              f"{len(scripts)} deploy files under {args.dir}")
        return 0
    if args.fleet_cmd == "watch":
        while True:
            print("\x1b[2J\x1b[H" + tn.format_stats(fl.watch_hosts(layout)))
            if args.once:
                return 0
            time.sleep(args.interval)
    if args.fleet_cmd == "bombard":
        sent = asyncio.run(
            fl.bombard_hosts(layout, args.rate, args.duration))
        print(f"submitted {sent} transactions")
        return 0
    if args.fleet_cmd == "health":
        return _print_health(fl, layout, args.json)
    if args.fleet_cmd == "trace":
        return _print_trace(fl, layout, args.txid, args.json)
    if args.fleet_cmd == "scrape":
        rows = fl.scrape_hosts(layout)
        if getattr(args, "rollup", False):
            rollup = fl.rollup_metrics(rows)
            # digest-anchor divergence comes from /healthz (a hash
            # cannot be a metric sample); best-effort — rollup output
            # must not require every node to serve the health surface
            try:
                hrows = fl.health_hosts(layout)
                # epoch divergence is already covered by the
                # babble_epoch series check above
                rollup["divergence"].extend(
                    d for d in fl.health_divergence(hrows)
                    if d["kind"] == "digest"
                )
            except Exception as e:
                rollup["health_error"] = str(e)
            if args.json:
                print(json.dumps(rollup, indent=1))
            else:
                print(fl.format_rollup(rollup))
            # a diverged fleet must fail the sweep the same way fleet
            # health would — CI scripted on this exit code must not
            # see green over a split committed history
            diverged = any(
                d.get("severity") == "error"
                for d in rollup["divergence"]
            )
            return 0 if not rollup["unparsed"] and not diverged else 1
        if getattr(args, "spans", False):
            # merge the span sweep into the metrics rows; span output is
            # structured (trees), so this mode is always JSON.  A
            # loopback-gated host's spans row carries kind='gated' —
            # expected policy, so it does not flip the exit code the way
            # a missing /metrics blob does.
            for row, srow in zip(rows, fl.scrape_spans(layout)):
                if "spans" in srow:
                    row["spans"] = srow["spans"]
                else:
                    row["spans_kind"] = srow["kind"]
                    row["spans_error"] = srow["error"]
            print(json.dumps(rows, indent=1))
            ok = all(
                "metrics" in r
                and ("spans" in r or r.get("spans_kind") == "gated")
                for r in rows
            )
            return 0 if ok else 1
        if args.json:
            print(json.dumps(rows, indent=1))
        else:
            # one exposition blob per host, comment-separated so the
            # output stays valid Prometheus text; failures go to stderr
            # and flip the exit code (a silent half-sweep reads as a
            # healthy fleet)
            for row in rows:
                if "metrics" in row:
                    print(f"# ==== {row['host']} ====")
                    print(row["metrics"], end="")
                else:
                    print(f"{row['host']}: {row['kind']}: {row['error']}",
                          file=sys.stderr)
        return 0 if all("metrics" in r for r in rows) else 1
    raise SystemExit(f"unknown fleet subcommand {args.fleet_cmd}")


def cmd_chaos(args) -> int:
    from .chaos import (
        CANNED,
        Scenario,
        canned_names,
        load_scenario,
        run_live,
        run_scenario,
    )

    if args.chaos_cmd == "list":
        for name in canned_names():
            sc = CANNED[name]
            print(f"{name}: {sc['nodes']} nodes, {sc['steps']} steps, "
                  f"engine={sc.get('engine', 'fused')}, "
                  f"invariants={','.join(sc['invariants'])}")
        return 0
    if args.chaos_cmd == "show":
        print(json.dumps(load_scenario(args.scenario).to_dict(), indent=1))
        return 0
    if args.chaos_cmd == "run":
        sc = load_scenario(args.scenario)
        overrides = {}
        if args.seed is not None:
            overrides["seed"] = args.seed
        if args.steps is not None:
            overrides["steps"] = args.steps
        if args.nodes is not None:
            overrides["nodes"] = args.nodes
        if overrides:
            sc = Scenario.from_dict({**sc.to_dict(), **overrides})
        if args.live:
            report = run_live(sc, args.dir)
            print(json.dumps(report, indent=1))
            return 0 if report.get("advanced") else 1
        result = run_scenario(sc)
        if args.json:
            print(json.dumps(result.to_dict(), indent=1))
        else:
            print(f"scenario {result.name} seed={result.seed} "
                  f"steps={result.steps}")
            print(f"fingerprint {result.fingerprint()}")
            print(f"faults injected: {result.fault_counts or '{}'}")
            print("consensus events: " + ", ".join(
                f"node{i}={c}"
                for i, c in sorted(result.consensus_counts_final.items())
            ))
            print(result.report.format())
        if not result.report.ok:
            print("CHAOS RUN FAILED: invariant violation(s) above",
                  file=sys.stderr)
        return 0 if result.report.ok else 1
    raise SystemExit(f"unknown chaos subcommand {args.chaos_cmd}")


def _cmd_lint_fallback(_args) -> int:
    # unreachable while main()'s `lint` interception exists (argparse
    # never sees the verb); calls the analysis CLI directly — never
    # back through main() — so it cannot recurse if that ever changes
    from .analysis.cli import main as lint_main

    return lint_main([])


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="babble-tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    kg = sub.add_parser("keygen", help="generate an ECDSA P-256 keypair")
    kg.add_argument("--datadir", default="", help="write priv_key.pem here")
    kg.set_defaults(fn=cmd_keygen)

    rn = sub.add_parser("run", help="run a consensus node")
    rn.add_argument("--datadir", default=".",
                    help="dir with priv_key.pem and peers.json")
    rn.add_argument("--node_addr", default="127.0.0.1:1337")
    rn.add_argument("--no_client", action="store_true",
                    help="use an in-memory app proxy instead of sockets")
    rn.add_argument("--proxy_addr", default="127.0.0.1:1338",
                    help="where we listen for the app's SubmitTx")
    rn.add_argument("--client_addr", default="127.0.0.1:1339",
                    help="the app's CommitTx server")
    rn.add_argument("--service_addr", default="127.0.0.1:8000")
    rn.add_argument("--allow_remote_debug", action="store_true",
                    help="serve /debug/* to non-loopback callers "
                         "(default: loopback only)")
    rn.add_argument("--log_level", default="info")
    rn.add_argument("--heartbeat", type=int, default=1000, help="ms")
    rn.add_argument("--max_pool", type=int, default=2)
    rn.add_argument("--tcp_timeout", type=int, default=1000, help="ms")
    rn.add_argument("--cache_size", type=int, default=500)
    rn.add_argument("--no_pipeline", action="store_true",
                    help="disable pipelined gossip (speculative push); "
                         "restores the lockstep pull exchange")
    rn.add_argument("--gossip_fanout", type=int, default=1,
                    help="peers gossiped per heartbeat tick")
    rn.add_argument("--gossip_inflight", type=int, default=4,
                    help="max concurrent outbound gossip exchanges")
    rn.add_argument("--no_eager_gossip", action="store_true",
                    help="don't launch the next gossip immediately when "
                         "one finishes with txs pooled")
    rn.add_argument("--coalesce_max", type=int, default=1024,
                    help="max client txs coalesced into one event")
    rn.add_argument("--coalesce_latency", type=int, default=50,
                    help="ms a pooled tx may wait before a self-parent "
                         "event is minted for it")
    rn.add_argument("--mint_backpressure", type=int, default=0,
                    help="pause deadline mints while undetermined "
                         "backlog exceeds this (0 = cache_size/4)")
    rn.add_argument("--submit_per_client", type=int, default=1024,
                    help="admission control: per-client submit queue cap")
    rn.add_argument("--submit_total", type=int, default=8192,
                    help="admission control: total submit queue cap")
    rn.add_argument("--submit_adaptive", action="store_true",
                    help="derive admission caps from the observed "
                         "commit drain rate (EWMA) instead of the "
                         "static caps")
    rn.add_argument("--bootstrap_peers", default="",
                    help="membership: path to the FOUNDING peers.json "
                         "when this node is a joiner (its own address "
                         "is only in the datadir peers.json; it "
                         "observes until its signed join tx commits)")
    rn.add_argument("--consensus_interval", type=int, default=0,
                    help="ms between consensus pipeline runs (0 = every sync)")
    rn.add_argument("--byzantine", action="store_true",
                    help="fork-aware live mode: accept + detect "
                         "equivocations instead of rejecting them")
    rn.add_argument("--fork_k", type=int, default=2,
                    help="branch slots per creator (fork budget K-1)")
    rn.add_argument("--fork_caps", default="",
                    help="pre-sized byzantine pipeline capacities "
                         "'e,s,r' (one jit shape at boot instead of "
                         "demand-driven growth recompiles)")
    rn.add_argument("--engine", default="fused",
                    choices=("fused", "wide"),
                    help="honest-mode engine: fused [E,N] coordinate "
                         "tensors, or the column-blocked rolling-window "
                         "wide engine (the 10k-participant layout)")
    rn.add_argument("--wide_caps", default="",
                    help="wide-engine window capacities 'e,s,r' "
                         "(fixed at boot; the engine compacts instead "
                         "of growing)")
    rn.add_argument("--seq_window", type=int, default=0,
                    help="per-creator rolling window (0 = cache_size)")
    rn.add_argument("--inactive_rounds", type=int, default=-1,
                    help="per-creator eviction: decided rounds of "
                         "silence before a creator's retained tail "
                         "evicts (its return then fast-forwards); "
                         "-1 = default 32, 0 = disabled")
    rn.add_argument("--no_ff_verify", action="store_true",
                    help="skip signed-state-proof verification on "
                         "fast-forward snapshots (trust any serving "
                         "peer — the pre-PR-8 model)")
    rn.add_argument("--anchor_interval", type=int, default=2048,
                    help="rolling attestation checkpoints: co-sign a "
                         "CommitDigest anchor with a peer quorum every "
                         "N commits (joiners verify deep fast-forwards "
                         "against it); 0 disables collection")
    rn.add_argument("--kernel_class", default="auto",
                    choices=("auto", "latency", "throughput"),
                    help="compiled-surface pin for the fused engine: "
                         "auto picks the small-batch latency kernel for "
                         "gossip-sized flushes, throughput for bulk")
    rn.add_argument("--no_packed_votes", action="store_true",
                    help="pin the pre-diet f32 vote tallies on the "
                         "fused latency kernel (bit-identical; the "
                         "packed popcount path is the default)")
    rn.add_argument("--no_frontier", action="store_true",
                    help="pin full-height fd scans in the windowed "
                         "order phase (bit-identical; the event-axis "
                         "frontier slice is the default)")
    rn.add_argument("--no_aot_prewarm", action="store_true",
                    help="skip AOT pre-compilation of recorded live-flush "
                         "shapes at boot (the persistent jit cache still "
                         "applies)")
    rn.add_argument("--jax_cache", default="",
                    help="jit cache dir ('' = $JAX_COMPILATION_CACHE_DIR, "
                         "else <checkout>/.jax_cache, or <datadir>/jax_cache "
                         "in an installed package; 'off' = disabled)")
    rn.add_argument("--checkpoint_dir", default="",
                    help="resume from + periodically checkpoint to this dir")
    rn.add_argument("--checkpoint_interval", type=float, default=30.0,
                    help="seconds between checkpoints")
    rn.add_argument("--wal_dir", default="",
                    help="per-event write-ahead log dir: restart replays "
                         "the tail on top of the newest checkpoint, so "
                         "the node resumes at its published head seq")
    rn.add_argument("--wal_fsync", default="batch",
                    help="WAL fsync policy: always | batch(n,ms) | off "
                         "(default batch = 64 appends / 50 ms)")
    rn.add_argument("--no_lineage", action="store_true",
                    help="disable commit-lineage tracing (per-tx/per-"
                         "event lifecycle ledgers behind /debug/lineage "
                         "and `fleet trace`)")
    rn.add_argument("--no_flight", action="store_true",
                    help="disable the flight recorder (state-transition "
                         "ring behind /debug/flight + crash dumps)")
    rn.add_argument("--phase_probe", action="store_true",
                    help="dispatch the fused latency flush as three "
                         "separately-timed sub-programs (ingest/fame/"
                         "order wall histograms; bit-identical results, "
                         "one host sync per phase — profiling posture)")
    rn.add_argument("--commit_slo", type=int, default=1000,
                    help="commit-latency SLO in ms for the /healthz "
                         "burn gauge")
    rn.add_argument("--chaos_plan", default="",
                    help="scenario/fault-plan JSON: wrap the transport "
                         "in a seeded FaultyTransport (chaos testing)")
    rn.add_argument("--chaos_seed", type=int, default=None,
                    help="override the chaos plan's seed")
    rn.add_argument("--chaos_epoch", type=float, default=None,
                    help="fleet-wide tick-0 (unix seconds) so restarted "
                         "nodes rejoin the shared chaos timeline "
                         "(default: this process's boot time)")
    rn.add_argument("--chaos_addrs", default="",
                    help="JSON map of gossip address -> scenario node "
                         "index (written by chaos run --live): the "
                         "exact link-identity source once joiners "
                         "exist; default derives identities from the "
                         "founding peer set")
    rn.set_defaults(fn=cmd_run)

    sm = sub.add_parser("sim", help="batch consensus over a generated DAG")
    sm.add_argument("--nodes", type=int, default=64)
    sm.add_argument("--events", type=int, default=16384)
    sm.add_argument("--rounds", type=int, default=256)
    sm.add_argument("--seed", type=int, default=7)
    sm.add_argument("--profile", default="",
                    help="write a jax profiler (xprof) trace to this dir")
    sm.set_defaults(fn=cmd_sim)

    dm = sub.add_parser("dummy", help="interactive chat client "
                        "(reference cmd/dummy_client)")
    dm.add_argument("--node_addr", default="127.0.0.1:1338",
                    help="the node's SubmitTx JSON-RPC server")
    dm.add_argument("--listen", default="127.0.0.1:1339",
                    help="where we serve the node's CommitTx calls")
    dm.add_argument("--log", default="messages.txt")
    dm.add_argument("--quiet", action="store_true",
                    help="no stdin/stdout chat; just serve commits")
    dm.set_defaults(fn=cmd_dummy)

    tnp = sub.add_parser("testnet", help="local fleet ops "
                         "(reference docker/scripts)")
    tsub = tnp.add_subparsers(dest="testnet_cmd", required=True)
    for name, hlp in (("conf", "write node datadirs + peers.json"),
                      ("run", "launch nodes + dummy apps"),
                      ("watch", "poll fleet /Stats"),
                      ("health", "one /healthz sweep + divergence table"),
                      ("trace", "stitch a tx's cross-node lineage"),
                      ("bombard", "flood random transactions")):
        sp = tsub.add_parser(name, help=hlp)
        sp.add_argument("--n", type=int, default=4)
        sp.add_argument("--dir", default="testnet-data")
        sp.add_argument("--base_port", type=int, default=12000)
        if name == "conf":
            sp.add_argument("--overwrite", action="store_true")
        if name == "health":
            sp.add_argument("--json", action="store_true")
        if name == "trace":
            sp.add_argument("txid", help="sha256 hex of the exact "
                                         "submitted tx bytes")
            sp.add_argument("--json", action="store_true")
        if name == "run":
            sp.add_argument("--heartbeat", type=int, default=10, help="ms")
            sp.add_argument("--no_clients", action="store_true")
            sp.add_argument("--interval", type=float, default=5.0)
        if name == "watch":
            sp.add_argument("--interval", type=float, default=2.0)
            sp.add_argument("--once", action="store_true")
        if name == "bombard":
            sp.add_argument("--rate", type=float, default=50.0, help="tx/s")
            sp.add_argument("--duration", type=float, default=10.0)
            sp.add_argument("--clients", type=int, default=1,
                            help=">1 uses the many-client harness "
                                 "(per-connection admission identities, "
                                 "overloaded-aware backoff)")
            sp.add_argument("--batch", type=int, default=1,
                            help="txs per Babble.SubmitTxBatch call "
                                 "(many-client harness only)")
        sp.set_defaults(fn=cmd_testnet)

    flp = sub.add_parser("fleet", help="multi-host fleet ops "
                         "(reference terraform/makefile + scripts)")
    fsub = flp.add_subparsers(dest="fleet_cmd", required=True)
    for name, hlp in (
        ("conf", "node datadirs + peers.json + ssh deploy scripts"),
        ("watch", "poll every host's /Stats"),
        ("scrape", "sweep every host's /metrics (Prometheus text)"),
        ("health", "sweep every host's /healthz into one fleet table "
                   "flagging epoch/lcr/digest divergence"),
        ("trace", "scrape + stitch one tx's cross-node commit lineage"),
        ("bombard", "flood transactions across the hosts"),
    ):
        sp = fsub.add_parser(name, help=hlp)
        sp.add_argument("--hosts", required=True,
                        help="file with one routable host address per "
                             "line ('host' or 'host:service_port' — the "
                             "latter for same-host fleets)")
        sp.add_argument("--dir", default="fleet-data")
        sp.add_argument("--gossip_port", type=int, default=1337)
        sp.add_argument("--submit_port", type=int, default=1338)
        sp.add_argument("--commit_port", type=int, default=1339)
        sp.add_argument("--service_port", type=int, default=8080)
        if name == "watch":
            sp.add_argument("--interval", type=float, default=2.0)
            sp.add_argument("--once", action="store_true")
        if name == "scrape":
            sp.add_argument("--json", action="store_true",
                            help="emit the sweep as a JSON row list "
                                 "instead of concatenated text")
            sp.add_argument("--spans", action="store_true",
                            help="also fetch each host's /debug/spans "
                                 "(loopback-gated hosts report kind="
                                 "'gated'); implies JSON output")
            sp.add_argument("--rollup", action="store_true",
                            help="aggregate per-node series into fleet "
                                 "sums/maxes with a divergence section "
                                 "(disagreeing babble_epoch / digest "
                                 "anchors render as warning rows)")
        if name == "health":
            sp.add_argument("--json", action="store_true")
        if name == "trace":
            sp.add_argument("txid", help="sha256 hex of the exact "
                                         "submitted tx bytes")
            sp.add_argument("--json", action="store_true")
        if name == "bombard":
            sp.add_argument("--rate", type=float, default=50.0, help="tx/s")
            sp.add_argument("--duration", type=float, default=10.0)
        sp.set_defaults(fn=cmd_fleet)

    chp = sub.add_parser("chaos", help="seeded fault injection + "
                         "consensus invariant checking (babble_tpu/chaos)")
    csub = chp.add_subparsers(dest="chaos_cmd", required=True)
    cl = csub.add_parser("list", help="list the canned scenarios")
    cl.set_defaults(fn=cmd_chaos)
    cs = csub.add_parser("show", help="print a scenario as JSON "
                         "(schema-by-example for custom plans)")
    cs.add_argument("scenario", help="canned name or scenario JSON path")
    cs.set_defaults(fn=cmd_chaos)
    cr = csub.add_parser("run", help="run a scenario and check its "
                         "invariants (exit 1 on violation)")
    cr.add_argument("scenario", help="canned name or scenario JSON path")
    cr.add_argument("--seed", type=int, default=None,
                    help="override the scenario seed (same seed = "
                         "bit-identical fault schedule + committed order)")
    cr.add_argument("--steps", type=int, default=None)
    cr.add_argument("--nodes", type=int, default=None)
    cr.add_argument("--json", action="store_true",
                    help="dump the full result (fault schedule, per-node "
                         "orders, invariant report) as JSON")
    cr.add_argument("--live", action="store_true",
                    help="run against a live subprocess testnet instead "
                         "of the deterministic in-memory cluster")
    cr.add_argument("--dir", default="chaos-data",
                    help="datadir for --live fleets")
    cr.set_defaults(fn=cmd_chaos)

    # `lint` never reaches argparse — the interception at the top of
    # main() forwards its whole tail verbatim (REMAINDER cannot capture
    # a leading option like `lint --json`).  Registered here only so the
    # verb appears in --help; the fn is a defensive fallback should the
    # interception ever move.
    lp = sub.add_parser(
        "lint",
        help="babble-lint static analysis (see python -m "
             "babble_tpu.analysis --help for the full surface)",
    )
    lp.set_defaults(fn=_cmd_lint_fallback)
    return p


def main(argv=None) -> int:
    # `lint` forwards verbatim BEFORE argparse sees the tail: REMAINDER
    # cannot capture a leading option (`lint --json ...`), and the
    # analysis CLI owns its whole surface anyway.
    raw = sys.argv[1:] if argv is None else list(argv)
    if raw and raw[0] == "lint":
        from .analysis.cli import main as lint_main

        return lint_main(raw[1:])
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
