"""The ``serve`` subcommand: online flow inference as a service.

Boots one replica (model + warm compiled-program pool; with ``--model``
given more than once or a ``models:`` list in the config, several models
behind one scheduler, all resident), then either:

- ``--prebuild``: compile and AOT-export every (model, bucket, wire)
  triple of the serve config — with ``--ladder``, every iteration-rung
  program too — and exit: the deploy-time warm-pool builder (a replica
  booting against the exported store serves its first request with zero
  compiles);
- default: run the built-in open-loop load generator against the
  scheduler and print the SLO report (p50/p99 latency, pairs/s,
  shed/error counts; with ``--ladder``, the per-class breakdown) as
  JSON — the in-process serving harness the network frontend will
  mount.

Knob precedence everywhere: CLI flag > config file (``serve:`` section)
> ``RMD_SERVE_*`` environment knob > registered default.
"""

import json
import logging
import os
import signal
import subprocess
import sys
import threading
from pathlib import Path

from .. import models, serve as serving, utils


def _pick(cli, cfg, cfg_key, env_value):
    if cli is not None:
        return cli
    if cfg_key in cfg:
        return cfg[cfg_key]
    return env_value


def _resolve(path, cfg_path):
    """Config-file-relative path resolution (same contract as the data
    layer's spec refs): a relative path inside the serve config means
    "next to this file", not "under whatever CWD the CLI ran from"."""
    if cfg_path is None or Path(path).is_absolute():
        return path
    return str(Path(cfg_path).parent / path)


def _cli_models(args):
    """The ``--model`` flags as a list (the flag appends)."""
    return list(getattr(args, "model", None) or ())


def serve(args):
    if getattr(args, "fleet", None):
        return _serve_fleet(args)

    utils.logging.setup()

    from .. import compile as programs, telemetry
    from ..utils import compcache, env

    tele = telemetry.get()
    if getattr(args, "telemetry", None):
        # serve uses the non-blocking sink: disk writes ride a bounded
        # background queue, a slow disk sheds trace events (counted)
        # instead of backpressuring the scheduler
        tele = telemetry.activate(
            telemetry.create(Path(args.telemetry), nonblocking=True))
        if tele.path:
            logging.info(f"writing telemetry events to '{tele.path}'")
    import jax

    from .train import describe_devices, select_devices

    devices = select_devices(args.device, args.device_ids)
    jax.config.update("jax_default_device", devices[0])
    where = describe_devices(devices)
    tele.emit(
        "boot",
        compile_cache=compcache.effective_dir(),
        aot_dir=str(programs.programs_dir()) if programs.aot_enabled()
        else None,
        aot=programs.aot_enabled(),
        **where,
    )
    logging.info("serving on {device_kind} [{platform}], default backend "
                 "'{backend}'".format(**where))

    cfg = {}
    if getattr(args, "config", None):
        cfg = utils.config.load(args.config)
        cfg = cfg.get("serve", cfg)

    from ..models.input import ShapeBuckets
    from ..models.wire import WireFormat

    # the models to hold: each with its buckets, batch size and checkpoint
    # (an entry of the config's 'models' list states its own; a --model
    # flag and the config's 'model' key take the server's)
    cfg_path = getattr(args, "config", None)
    cli_models = _cli_models(args)
    if cli_models:
        entries = [{"model": m} for m in cli_models]
    elif cfg.get("models"):
        entries = [dict(e, model=_resolve(e["model"], cfg_path)
                        if isinstance(e.get("model"), str) else e.get("model"))
                   for e in cfg["models"]]
    else:
        src = cfg.get("model")
        entries = [{"model": _resolve(src, cfg_path)
                    if isinstance(src, str) else src}]
    if any(e.get("model") is None for e in entries):
        raise ValueError("serve needs a model: --model, the config's "
                         "'model' key or its 'models' list")
    several = len(entries) > 1
    if several and args.checkpoint is not None:
        raise ValueError("--checkpoint is one model's: give each entry of "
                         "the config's 'models' list its own 'checkpoint'")

    held = []   # (spec, buckets, batch size, checkpoint) a model
    for entry in entries:
        model_cfg = (utils.config.load(entry["model"])
                     if isinstance(entry["model"], str) else entry["model"])
        if "strategy" in model_cfg:
            model_cfg = model_cfg["model"]
        spec = models.load(model_cfg)
        logging.info(f"serving model '{spec.id}'")

        buckets_spec = _pick(args.buckets, entry, "buckets", _pick(
            None, cfg, "buckets", env.raw("RMD_SERVE_BUCKETS")))
        buckets = ShapeBuckets.from_config(buckets_spec)
        if buckets is None or not buckets.sizes:
            raise ValueError(
                "serve needs explicit bucket sizes: --buckets 'HxW,...', "
                "the config's 'buckets' key, or RMD_SERVE_BUCKETS")
        logging.info(f"shape buckets: {buckets.describe()}")

        batch_size = int(_pick(args.batch_size, entry, "batch-size", _pick(
            None, cfg, "batch-size", env.get_int("RMD_SERVE_BATCH"))))
        checkpoint = args.checkpoint
        if checkpoint is None:
            checkpoint = entry.get("checkpoint", cfg.get("checkpoint"))
            if checkpoint is not None:
                checkpoint = _resolve(checkpoint, cfg_path)
        held.append((spec, buckets, batch_size, checkpoint))
    if len({spec.id for spec, *_ in held}) < len(held):
        raise ValueError("two of the models to serve share one id: "
                         f"{[spec.id for spec, *_ in held]}")

    wire_cfg = _pick(getattr(args, "wire_format", None), cfg, "wire-format",
                     env.get_str("RMD_WIRE_FORMAT"))
    wire = WireFormat.from_config(wire_cfg)
    if wire is not None:
        logging.info(f"request wire format: {wire.describe()}")

    ladder_spec = _pick(getattr(args, "ladder", None), cfg, "ladder", None)
    ladder = None
    if ladder_spec:
        ladder = serving.LadderSpec.from_config(
            ladder_spec, threshold=_pick(
                getattr(args, "ladder_threshold", None), cfg,
                "ladder-threshold", None))
        logging.info(f"iteration ladder: {ladder.describe()}")

    video = bool(_pick(getattr(args, "video", None) or None, cfg,
                       "video", None))
    if video:
        logging.info("video sessions enabled: warm-start programs + "
                     "sticky per-client carry cache")

    quant = _pick(getattr(args, "quant", None), cfg, "quant",
                  env.get_str("RMD_QUANT"))
    if quant:
        logging.info(f"quantized matching tier: {quant} (fast class + "
                     "video warm frames)")

    if several and (ladder is not None or video or quant
                    or getattr(args, "listen_port", None) is not None):
        raise ValueError(
            "a server of several models serves no --ladder, --video or "
            "--quant and is no fleet replica: those are one model's "
            "server's")

    sessions = {
        spec.id: serving.ServeSession(
            spec, buckets, wire=wire, checkpoint=checkpoint,
            batch_size=batch_size, ladder=ladder, video=video, quant=quant)
        for spec, buckets, batch_size, checkpoint in held}
    # one model: the session itself, as ever
    session = sessions if several else next(iter(sessions.values()))

    aot_store = getattr(args, "aot_store", None)
    if aot_store and not getattr(args, "prebuild", False) \
            and programs.aot_enabled():
        fetched = programs.fetch(aot_store)
        logging.info(
            f"AOT store '{aot_store}': fetched {fetched['copied']} "
            f"programs ({fetched['present']} already local)")

    outcomes = [o for s in sessions.values() for o in s.warm_pool()]
    for o in outcomes:
        rung = f" rung {o['rung']}" if "rung" in o else ""
        logging.info(
            f"warm pool: {o['model']} bucket {o['bucket']} batch "
            f"{o['batch']}{rung} [{o['wire']}] — {o['compiles']} compiles, "
            f"{o['aot_hits']} AOT hits, {o['aot_saves']} AOT saves "
            f"({o['seconds']:.2f} s)")

    if getattr(args, "prebuild", False):
        published = None
        if aot_store and programs.aot_enabled():
            published = programs.publish(aot_store)
            logging.info(
                f"AOT store '{aot_store}': published "
                f"{published['copied']} programs "
                f"({published['present']} already there)")
        print(json.dumps({"prebuild": outcomes, "published": published}))
        if getattr(args, "telemetry", None):
            telemetry.deactivate()
        return

    max_wait_ms = float(_pick(args.max_wait_ms, cfg, "max-wait-ms",
                              env.get_float("RMD_SERVE_MAX_WAIT_MS")))
    queue_limit = int(_pick(args.queue_limit, cfg, "queue-limit",
                            env.get_int("RMD_SERVE_QUEUE")))

    # each model's batch size is its session's
    scheduler = serving.Scheduler(
        session, max_wait_ms=max_wait_ms, queue_limit=queue_limit).start()

    if getattr(args, "listen_port", None) is not None:
        _serve_replica_blocking(args, session, scheduler, tele)
        if getattr(args, "telemetry", None):
            telemetry.deactivate()
        return

    metrics_port = int(_pick(getattr(args, "metrics_port", None), cfg,
                             "metrics-port",
                             env.get_int("RMD_METRICS_PORT")) or 0)
    observer = None
    if metrics_port:
        observer = serving.serve_observer(
            session, scheduler, metrics_port, sink=tele)
        logging.info(
            f"observability plane at {observer.url}: /metrics /healthz "
            f"/statusz /profilez")

    # built-in open-loop client: every bucket size plus an off-bucket
    # variant of each (exercises quantization + partial batches); with
    # several models each in turn, over its own buckets
    by_model = []
    for name, s in sessions.items():
        shapes = []
        for h, w in s.buckets.sizes:
            shapes.append((h, w))
            if h > 8 and w > 8:
                shapes.append((h - 8, w - 8))
        by_model.append((name, shapes))

    requests = int(_pick(args.requests, cfg, "requests", 32))
    rate = float(_pick(args.rate, cfg, "rate", 50.0))
    classes = list(serving.CLASSES) if ladder is not None else None
    if video:
        # sticky streams force the fast rung; class cycling is moot
        classes = None
    if several:
        logging.info(f"open-loop load over {len(by_model)} models in turn: "
                     f"{[name for name, _ in by_model]}")
    logging.info(f"open-loop load: {requests} requests at {rate}/s over "
                 f"{len(shapes)} shapes"
                 + (f", classes {'/'.join(classes)}" if classes else "")
                 + (", sticky video streams" if video else ""))

    report = serving.loadgen.run_open_loop(
        scheduler, shapes, requests=requests, rate_hz=rate, classes=classes,
        sequence=video, models=by_model if several else None)
    if scheduler.slo:
        report["slo"] = scheduler.slo.snapshot()
    tail = scheduler.trace_summary.tail()
    if tail is not None:
        report["tail"] = tail
    scheduler.stop(drain=True)

    logging.info(
        f"served {report['completed']}/{report['requests']} requests: "
        f"p50 {report['p50_ms']:.1f} ms, p99 {report['p99_ms']:.1f} ms, "
        f"{report['pairs_per_sec']:.2f} pairs/s")
    print(json.dumps(report))

    if observer is not None:
        observer.close()
    if getattr(args, "telemetry", None):
        telemetry.deactivate()

    # sheds and malformed requests are the server doing its job; a
    # failed device dispatch is the server failing at it
    internal = report["errors"].get("internal", 0)
    if internal:
        sys.exit(f"serve: {internal} of {report['requests']} requests "
                 f"ended with an internal error (failed device dispatch)")


def _serve_replica_blocking(args, session, scheduler, tele):
    """Replica mode: bind the fleet API, write the port-file rendezvous,
    block until SIGTERM/SIGINT, then drain and exit cleanly."""
    from .. import fleet

    index = int(getattr(args, "replica_index", 0) or 0)
    observer = serving.Observer(session, scheduler, sink=tele)
    server = fleet.serve_replica(
        session, scheduler, observer, int(args.listen_port), index=index)
    logging.info(
        f"replica {index} serving at {server.url}: /v1/flow /sessionz "
        f"/drainz + /metrics /healthz /statusz /profilez")
    port_file = getattr(args, "port_file", None)
    if port_file:
        # atomic write: the supervisor polls this file and must never
        # read a torn port number
        tmp = f"{port_file}.tmp"
        Path(tmp).write_text(f"{server.port}\n")
        os.replace(tmp, port_file)

    stop = threading.Event()

    def _terminate(signum, frame):
        logging.info(f"replica {index}: signal {signum}, draining")
        observer.begin_drain()
        stop.set()

    signal.signal(signal.SIGTERM, _terminate)
    signal.signal(signal.SIGINT, _terminate)
    while not stop.wait(1.0):
        pass
    scheduler.stop(drain=True)
    server.close()
    logging.info(f"replica {index}: drained and stopped")


def _child_argv(extra):
    """The replica child's command line: this CLI re-entered with the
    parent's serve flags minus the fleet-harness-only ones."""
    strip_valued = {"--fleet", "--telemetry", "--metrics-port",
                    "--listen-port", "--port-file", "--replica-index"}
    strip_flags = {"--drill", "--prebuild"}
    argv, skip = [], False
    for a in sys.argv[1:]:
        if skip:
            skip = False
            continue
        opt = a.split("=", 1)[0]
        if opt in strip_flags:
            continue
        if opt in strip_valued:
            skip = "=" not in a
            continue
        argv.append(a)
    head = [sys.executable]
    script = sys.argv[0]
    if script and script.endswith(".py") and Path(script).exists():
        head.append(script)
    else:
        head += ["-c",
                 "from raft_meets_dicl_tpu.main import main; main()"]
    return head + argv + extra


def _check_fleet_platform(n, device):
    """Replicas are processes and each claims its platform's default
    devices with no device visibility set — on an accelerator that is
    every chip of the host, and a chip belongs to one process. More
    than one replica therefore only works on the CPU platform; say so
    at once (the parent cannot look: it must not initialise jax itself)
    instead of waiting out the boot deadline on a child that hangs.
    One process per host driving a replica per device is ROADMAP D6."""
    platform = device or os.environ.get("JAX_PLATFORMS", "")
    if n > 1 and platform.split(",")[0].strip() != "cpu":
        raise ValueError(
            f"--fleet {n}: replica processes would each claim the "
            f"accelerator, which belongs to one process at a time. Run "
            f"--fleet 1, or name the CPU platform (--device cpu or "
            f"JAX_PLATFORMS=cpu)")


def _serve_fleet(args):
    """Fleet mode: supervise N replica processes behind the router,
    then drive them (open-loop load or the kill/rejoin drill)."""
    utils.logging.setup()

    from .. import fleet, telemetry
    from ..models.input import ShapeBuckets
    from ..models.wire import WireFormat
    from ..utils import env

    tele = telemetry.get()
    if getattr(args, "telemetry", None):
        tele = telemetry.activate(
            telemetry.create(Path(args.telemetry), nonblocking=True))
        if tele.path:
            logging.info(f"writing telemetry events to '{tele.path}'")

    cfg = {}
    if getattr(args, "config", None):
        cfg = utils.config.load(args.config)
        cfg = cfg.get("serve", cfg)
    if len(_cli_models(args)) > 1 or len(cfg.get("models") or ()) > 1:
        raise ValueError(
            "--fleet: a replica holds one model; a server of several "
            "models is one process (serve without --fleet)")
    buckets = ShapeBuckets.from_config(
        _pick(args.buckets, cfg, "buckets", env.raw("RMD_SERVE_BUCKETS")))
    if buckets is None or not buckets.sizes:
        raise ValueError(
            "fleet mode needs explicit bucket sizes: --buckets 'HxW,...', "
            "the config's 'buckets' key, or RMD_SERVE_BUCKETS")
    wire = WireFormat.from_config(
        _pick(getattr(args, "wire_format", None), cfg, "wire-format",
              env.get_str("RMD_WIRE_FORMAT")))
    ladder_spec = _pick(getattr(args, "ladder", None), cfg, "ladder", None)
    video = bool(_pick(getattr(args, "video", None) or None, cfg,
                       "video", None))

    n = int(args.fleet) if int(args.fleet) > 0 \
        else env.get_int("RMD_FLEET_REPLICAS")
    _check_fleet_platform(n, args.device)
    logging.info(f"fleet: {n} replicas, buckets {buckets.describe()}"
                 + (f", wire {wire.describe()}" if wire else ""))

    def spawn(index, port_file):
        argv = _child_argv(["--listen-port", "0",
                            "--port-file", port_file,
                            "--replica-index", str(index)])
        return subprocess.Popen(argv, env=os.environ.copy())

    codec = fleet.EdgeCodec(buckets, wire=wire)
    router = fleet.Router(codec).start()
    sup = fleet.Supervisor(
        spawn, n,
        on_up=lambda i, url: router.add_replica(f"replica-{i}", url),
        on_down=lambda i: router.mark_down(f"replica-{i}"))
    router.on_recycle = lambda name: sup.recycle(
        int(name.rsplit("-", 1)[1]))  # graftlint: disable=host-sync -- parses a replica name, not a device value

    frontend = None
    report = {}
    try:
        sup.start(wait_ready=True)
        for slot in sup.slots:
            if slot.url:
                router.add_replica(slot.name, slot.url)
        ready = sum(1 for s in router.replicas().values() if s.eligible())
        if ready == 0:
            raise RuntimeError("fleet: no replica came up healthy")
        logging.info(f"fleet: {ready}/{n} replicas ready")

        metrics_port = int(_pick(getattr(args, "metrics_port", None), cfg,
                                 "metrics-port",
                                 env.get_int("RMD_METRICS_PORT")) or 0)
        if metrics_port:
            frontend = fleet.serve_frontend(router, metrics_port)
            logging.info(f"fleet front-end at {frontend.url}: /v1/flow "
                         f"/fleetz /healthz")

        shapes = []
        for h, w in buckets.sizes:
            shapes.append((h, w))
            if h > 8 and w > 8:
                shapes.append((h - 8, w - 8))
        classes = list(serving.CLASSES) if ladder_spec else None
        if video:
            classes = None

        if getattr(args, "drill", False):
            def kill(owner):
                index = int(owner.rsplit("-", 1)[1]) if owner else 0  # graftlint: disable=host-sync -- parses a replica name, not a device value
                logging.info(f"drill: hard-killing replica-{index}")
                sup.kill(index)
                return f"replica-{index}"

            report = fleet.run_drill(
                router, kill, shapes,
                classes=tuple(classes) if classes else (None,),
                frames=int(_pick(args.requests, cfg, "requests", 24)))
            report = {"fleet": n, "drill": report}
        else:
            requests = int(_pick(args.requests, cfg, "requests", 32))
            rate = float(_pick(args.rate, cfg, "rate", 50.0))
            report = serving.loadgen.run_open_loop(
                router, shapes, requests=requests, rate_hz=rate,
                classes=classes, sequence=video)
            report = {"fleet": n, **report}
    finally:
        report["router"] = router.describe()
        report["supervisor"] = sup.describe()
        if frontend is not None:
            frontend.close()
        router.stop()
        sup.stop()

    print(json.dumps(report))
    if getattr(args, "telemetry", None):
        telemetry.deactivate()
