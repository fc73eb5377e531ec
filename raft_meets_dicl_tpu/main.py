"""CLI argument parsing and dispatch.

Flag-surface parity with the reference (src/main.py:34-117); device flags
select the jax platform / device subset instead of CUDA ordinals, and
``--detect-anomaly`` maps to ``jax_debug_nans``.

Example usage:
- basic training
    ./main.py train --data strategy.yaml --model model.yaml
    ./main.py train --config config.json
- warm start (weights only) vs resume (full state)
    ./main.py train -d data.yaml -m model.yaml --checkpoint chkpt.ckpt
    ./main.py train --config config.json --resume chkpt.ckpt
- evaluation with report + flow images
    ./main.py evaluate -d data.yaml -m model.yaml -c chkpt.ckpt -o report.json
- checkpoint management
    ./main.py checkpoint info runs/<ts>/checkpoints --sort '{m_EndPointError_mean}'
    ./main.py checkpoint trim dir/ --compare '{m_EndPointError_mean}' --keep-best 5
- full-config generation
    ./main.py gencfg -o full.json -d strategy.yaml -m model.yaml
"""

import argparse

from . import cmd


def main():
    def fmtcls(prog):
        return argparse.HelpFormatter(prog, max_help_position=42)

    parser = argparse.ArgumentParser(
        description="Optical Flow Estimation (TPU-native)", formatter_class=fmtcls
    )
    subp = parser.add_subparsers(dest="command", help="help for command")

    # subcommand: train
    train = subp.add_parser("train", aliases=["t"], formatter_class=fmtcls,
                            help="train model")
    train.add_argument("-c", "--config", help="full training configuration")
    train.add_argument("-d", "--data", help="training strategy and data")
    train.add_argument("-m", "--model", help="specification of the model")
    train.add_argument("-s", "--seeds", help="seed config for initializing RNGs")
    train.add_argument("-i", "--inspect", help="specification of metrics")
    train.add_argument("-e", "--env", "--environment", dest="env",
                       help="environment config")
    train.add_argument("-o", "--output", default="runs",
                       help="base output directory [default: %(default)s]")
    train.add_argument("--device",
                       help="jax platform to use (tpu, cpu) [default: backend default]")
    train.add_argument("--device-ids",
                       help="comma-separated device indices for the SPMD data mesh")
    train.add_argument("--checkpoint",
                       help="start with pre-trained model state from checkpoint")
    train.add_argument("--resume",
                       help="resume training from checkpoint (full state); "
                            "'auto' discovers the newest valid checkpoint "
                            "(emergency saves included) under the output "
                            "directory, quarantining corrupt files")
    train.add_argument("--nonfinite", choices=["raise", "skip", "rollback"],
                       help="non-finite step recovery policy: raise (abort, "
                            "default), skip (drop the poisoned optimizer "
                            "update on device and continue), rollback "
                            "(skip, then restore the last valid checkpoint "
                            "when trips persist). Also: RMD_NONFINITE or "
                            "the env config's 'nonfinite' section")
    train.add_argument("--start-stage", type=int,
                       help="start with specified stage and skip previous")
    train.add_argument("--start-epoch", type=int,
                       help="start with specified epoch and skip previous")
    train.add_argument("--reproduce", action="store_true", help="use seeds from config")
    train.add_argument("--debug", action="store_true", help="enter debugger on exception")
    train.add_argument("--detect-anomaly", action="store_true",
                       help="enable jax nan-debugging (jax_debug_nans)")
    train.add_argument("--suffix", "--sfx", dest="suffix",
                       help="suffix for output directory")
    train.add_argument("--comment", dest="comment", help="comment to add to config file")
    train.add_argument("--limit-steps", type=int, dest="steps",
                       help="limit to a fixed number of steps")
    train.add_argument("--distributed", action="store_true",
                       help="join the multi-process runtime "
                            "(jax.distributed.initialize; on TPU pods "
                            "coordinator/rank are auto-discovered)")
    train.add_argument("--dist-coordinator", metavar="HOST:PORT",
                       help="coordinator address for non-TPU setups")
    train.add_argument("--dist-num-processes", type=int,
                       help="total process count for non-TPU setups")
    train.add_argument("--dist-process-id", type=int,
                       help="this process's id for non-TPU setups")
    train.add_argument("--profile", metavar="DIR",
                       help="capture a jax.profiler trace of the run into DIR "
                            "(open with TensorBoard's profile plugin); "
                            "combine with --limit-steps")
    train.add_argument("--telemetry", metavar="PATH",
                       help="telemetry JSONL sink path "
                            "[default: <run-dir>/events.jsonl]")
    train.add_argument("--compile-cache", metavar="DIR",
                       help="persistent XLA compile cache directory "
                            "(also: RMD_COMPILE_CACHE; "
                            "RMD_NO_COMPILE_CACHE=1 configures none; "
                            "all yield to JAX_COMPILATION_CACHE_DIR) "
                            "[default: <repo>/.jax_cache]. The AOT "
                            "program store lives in DIR/programs "
                            "(RMD_AOT=0 disables, RMD_AOT_DIR relocates)")
    train.add_argument("--no-telemetry", action="store_true",
                       help="disable run telemetry "
                            "(equivalent to RMD_TELEMETRY=0)")
    train.add_argument("--metrics-port", type=int, metavar="PORT",
                       help="trainer observability HTTP port on "
                            "127.0.0.1: /metrics (Prometheus text), "
                            "/healthz, /statusz, /profilez?seconds=N; "
                            "0 picks an ephemeral port (also: "
                            "RMD_TRAIN_METRICS_PORT) [default: off]")
    train.add_argument("--wire-format", choices=["f32", "bf16", "u8"],
                       help="host->device batch wire format: compact image "
                            "dtype + on-device normalization (also: "
                            "RMD_WIRE_FORMAT or the env config's 'wire' "
                            "section) [default: host-normalized f32]")
    train.add_argument("--loader-procs", type=int, metavar="N",
                       help="decode the input pipeline in N worker "
                            "processes (shared-memory transport); 0 = "
                            "thread pool (also: RMD_LOADER_PROCS)")
    train.add_argument("--mesh", metavar="DATA,MODEL",
                       help="SPMD mesh shape: 'D,M' (e.g. '4,2') builds a "
                            "2-D data×model mesh whose model axis shards "
                            "param/optimizer storage (regex partition "
                            "rules, parallel.partition); 'data' or unset "
                            "keeps the 1-D replicated-params data mesh; "
                            "D=-1 fills the remaining devices (also: "
                            "RMD_MESH or the env config's 'parallel' "
                            "section)")
    train.add_argument("--device-aug", action="store_true", dest="device_aug",
                       help="compile the augmentation pipeline into the "
                            "train step (on-device data engine): one fused "
                            "inverse-affine warp + elementwise photometric "
                            "ops under per-sample (sample_id, epoch) keys "
                            "(also: RMD_DEVICE_AUG or the env config's "
                            "'augment' section, which tunes the parameters)")
    train.add_argument("--accumulate", type=int, metavar="K",
                       help="in-step gradient accumulation: scan K "
                            "microbatches per optimizer step inside the "
                            "jitted train step — K× effective batch at "
                            "one microbatch's activation memory (also: "
                            "RMD_ACCUMULATE or the env config's "
                            "'parallel' section)")

    # subcommand: evaluate
    eval_ = subp.add_parser("evaluate", aliases=["e", "eval"], formatter_class=fmtcls,
                            help="evaluate model")
    eval_.add_argument("-d", "--data", required=True, help="evaluation dataset")
    eval_.add_argument("-m", "--model", required=True, help="the model to use")
    eval_.add_argument("-c", "--checkpoint", required=True, help="the checkpoint to load")
    eval_.add_argument("-b", "--batch-size", type=int, default=1,
                       help="batch-size to use for evaluation")
    eval_.add_argument("--iterations", type=int,
                       help="recurrence iteration override for the "
                            "model's update loop (also: RMD_ITERATIONS) "
                            "[default: model config]")
    eval_.add_argument("-x", "--metrics",
                       help="specification of metrics to use for evaluation")
    eval_.add_argument("-o", "--output",
                       help="write detailed output to this file (json or yaml)")
    eval_.add_argument("--incremental", metavar="PATH",
                       help="append per-sample metrics to this JSONL as the "
                            "sweep runs, so a crash keeps partial results "
                            "[default: <output>.samples.jsonl when -o is "
                            "set]")
    eval_.add_argument("--no-incremental", action="store_true",
                       help="disable the incremental per-sample JSONL")
    eval_.add_argument("-f", "--flow",
                       help="compute and write flow images to specified directory")
    from .cmd.eval import FLOW_FORMATS

    eval_.add_argument("--flow-format", default="visual:flow",
                       choices=FLOW_FORMATS, metavar="FORMAT",
                       help="output format for flow images [default: %(default)s]")
    eval_.add_argument("--flow-mrm", type=float,
                       help="maximum range of motion for visual flow image output")
    eval_.add_argument("--flow-gamma", type=float,
                       help="gamma for visual:flow image output")
    eval_.add_argument("--flow-transform",
                       help="transform for visual:flow:dark image output")
    eval_.add_argument("--flow-only", action="store_true",
                       help="only compute flow images, do not evaluate metrics")
    eval_.add_argument("--fwbw", action="store_true",
                       help="also run the reversed pair per sample and "
                            "derive forwards-backwards consistency "
                            "products (occlusion masks + confidence; "
                            "enables the visual:occlusion and "
                            "visual:confidence flow formats)")
    eval_.add_argument("--epe-cmap", default="gray",
                       help="colormap for end-point-error visualization")
    eval_.add_argument("--epe-max", type=float, default=None,
                       help="maximum end point error for visualization")
    eval_.add_argument("--device",
                       help="jax platform to use (tpu, cpu) [default: backend default]")
    eval_.add_argument("--device-ids",
                       help="comma-separated device indices")
    eval_.add_argument("--wire-format", choices=["f32", "bf16", "u8"],
                       help="host->device batch wire format (compact image "
                            "dtype, on-device normalization) "
                            "[default: host-normalized f32]")
    eval_.add_argument("--buckets", metavar="SPEC",
                       help="shape buckets for mixed-resolution datasets: "
                            "'group' (batch same-shape samples) or a "
                            "comma-separated HxW list, e.g. "
                            "'384x1280,448x1024' (quantize + batch; at "
                            "most one jit compile per bucket). Also: "
                            "RMD_EVAL_BUCKETS")
    eval_.add_argument("--precompile", action="store_true",
                       help="compile every declared bucket shape before "
                            "the sweep (requires explicit --buckets sizes)")
    eval_.add_argument("--compile-cache", metavar="DIR",
                       help="persistent XLA compile cache directory "
                            "(also: RMD_COMPILE_CACHE; yields to "
                            "JAX_COMPILATION_CACHE_DIR) "
                            "[default: <repo>/.jax_cache]; AOT program "
                            "store in DIR/programs (RMD_AOT=0 disables)")
    eval_.add_argument("--telemetry", metavar="PATH",
                       help="write sweep telemetry events (eval stats, "
                            "compile attribution, AOT hits/misses) to "
                            "this JSONL file")

    # subcommand: serve
    serve = subp.add_parser("serve", formatter_class=fmtcls,
                            help="serve flow inference (continuous "
                                 "shape-bucketed batching)")
    serve.add_argument("-c", "--config",
                       help="serve configuration (yaml/json with a "
                            "'serve' section; CLI flags win)")
    serve.add_argument("-m", "--model", action="append",
                       help="model specification to serve; given more "
                            "than once, one server holds every model "
                            "(each under the same --buckets and batch "
                            "size; per model: the config's 'models' list)")
    serve.add_argument("--checkpoint", help="checkpoint to load")
    serve.add_argument("--buckets", metavar="SPEC",
                       help="canonical request shapes, comma-separated "
                            "HxW list, e.g. '384x1280,448x1024' "
                            "(required; also: RMD_SERVE_BUCKETS or the "
                            "config's 'buckets' key)")
    serve.add_argument("--wire-format", choices=["f32", "bf16", "u8"],
                       help="request wire format: compact image dtype "
                            "decoded inside the jitted program "
                            "[default: host-normalized f32]")
    serve.add_argument("-b", "--batch-size", type=int,
                       help="device batch size per dispatch (also: "
                            "RMD_SERVE_BATCH) [default: 4]")
    serve.add_argument("--max-wait-ms", type=float,
                       help="max time a partial batch waits before "
                            "dispatching padded (also: "
                            "RMD_SERVE_MAX_WAIT_MS) [default: 50]")
    serve.add_argument("--queue-limit", type=int,
                       help="per-bucket admission queue bound; overload "
                            "sheds with a typed rejection (also: "
                            "RMD_SERVE_QUEUE) [default: 64]")
    serve.add_argument("--ladder", nargs="?", const=True, metavar="RUNGS",
                       help="serve latency classes (fast/balanced/"
                            "quality) over an iteration ladder; optional "
                            "ascending rung budgets, e.g. '4,8,12' "
                            "(also: RMD_LADDER, the config's 'ladder' "
                            "key) [default: off]")
    serve.add_argument("--ladder-threshold", type=float,
                       help="flow-delta norm below which the balanced "
                            "class stops escalating (also: "
                            "RMD_LADDER_THRESHOLD) [default: 0.1]")
    serve.add_argument("--video", action="store_true",
                       help="video sessions: register the warm-start "
                            "program per bucket, cache per-client carry "
                            "state (bounded + TTL-evicted), and route "
                            "sequence requests onto it; the built-in "
                            "client then submits sticky frame streams "
                            "(also: the config's 'video' key) "
                            "[default: off]")
    serve.add_argument("--quant", nargs="?", const="u8",
                       choices=["u8", "i8", "off"], metavar="MODE",
                       help="quantized matching tier for the fast ladder "
                            "class and video warm frames: correlation "
                            "volumes stored u8/i8 and dequantized "
                            "in-register by the lookup ('u8' when given "
                            "bare; also: RMD_QUANT, the config's 'quant' "
                            "key) [default: off]")
    serve.add_argument("--prebuild", action="store_true",
                       help="compile + AOT-export every (model, bucket, "
                            "wire) program triple — with --ladder, every "
                            "rung program too — and exit (deploy-time "
                            "warm-pool build)")
    serve.add_argument("--requests", type=int,
                       help="built-in open-loop client: request count "
                            "[default: 32]")
    serve.add_argument("--rate", type=float,
                       help="built-in open-loop client: submissions/s "
                            "[default: 50]")
    serve.add_argument("--device",
                       help="jax platform to use (tpu, cpu) [default: backend default]")
    serve.add_argument("--device-ids",
                       help="comma-separated device indices")
    serve.add_argument("--compile-cache", metavar="DIR",
                       help="persistent XLA compile cache directory "
                            "(also: RMD_COMPILE_CACHE; yields to "
                            "JAX_COMPILATION_CACHE_DIR) "
                            "[default: <repo>/.jax_cache]; AOT program "
                            "store in DIR/programs (RMD_AOT=0 disables)")
    serve.add_argument("--telemetry", metavar="PATH",
                       help="write serve telemetry events (request "
                            "spans, batches, rejects, warm-pool "
                            "outcomes) to this JSONL file")
    serve.add_argument("--metrics-port", type=int, metavar="PORT",
                       help="observability HTTP port on 127.0.0.1: "
                            "/metrics (Prometheus text), /healthz, "
                            "/statusz, /profilez?seconds=N (also: "
                            "RMD_METRICS_PORT, the config's "
                            "'metrics-port' key) [default: off]")
    serve.add_argument("--fleet", type=int, metavar="N",
                       help="fault-tolerant fleet: supervise N replica "
                            "processes behind the routing front-end "
                            "(least-loaded dispatch, retry, drain, "
                            "session handoff; also: RMD_FLEET_REPLICAS) "
                            "[default: single process]")
    serve.add_argument("--drill", action="store_true",
                       help="with --fleet: run the kill/rejoin chaos "
                            "drill instead of the plain open-loop client "
                            "(hard-kills a replica mid-stream, asserts "
                            "typed sheds only, <=1 cold frame, warm "
                            "rejoin)")
    serve.add_argument("--aot-store", metavar="DIR",
                       help="published AOT program store: --prebuild "
                            "publishes built programs into DIR; a "
                            "booting replica fetches from DIR before "
                            "warming (zero-compile boot)")
    serve.add_argument("--listen-port", type=int, metavar="PORT",
                       help="replica mode: serve the fleet API "
                            "(/v1/flow /sessionz /drainz + the "
                            "observability routes) on this 127.0.0.1 "
                            "port (0 = ephemeral) and block until "
                            "SIGTERM drains")
    serve.add_argument("--port-file", metavar="PATH",
                       help="replica mode: write the bound port here "
                            "once serving (the supervisor's rendezvous)")
    serve.add_argument("--replica-index", type=int, default=0,
                       metavar="I",
                       help="replica mode: this replica's fleet slot "
                            "index (labels telemetry + chaos triggers)")

    # subcommand: checkpoint
    chkpt = subp.add_parser("checkpoint", formatter_class=fmtcls,
                            help="inspect and manage checkpoints")
    chkpt_sub = chkpt.add_subparsers(dest="subcommand", help="help for subcommand")

    chkpt_info = chkpt_sub.add_parser("info", formatter_class=fmtcls,
                                      help="show info on checkpoint(s)")
    chkpt_info.add_argument("file", nargs="+",
                            help="checkpoint file or directory to search")
    chkpt_info.add_argument("--sort",
                            help="expression(s) for sorting checkpoints (comma-separated)")

    chkpt_trim = chkpt_sub.add_parser("trim", formatter_class=fmtcls,
                                      help="remove bad and/or outdated checkpoints")
    chkpt_trim.add_argument("directory", nargs="+",
                            help="directory to search for checkpoints")
    chkpt_trim.add_argument("--compare",
                            help="expression(s) for comparing checkpoints (comma-separated)")
    chkpt_trim.add_argument("--keep-latest", type=int,
                            help="keep specified number of latest checkpoints")
    chkpt_trim.add_argument("--keep-best", type=int,
                            help="keep specified number of best checkpoints")

    # subcommand: gencfg
    gencfg = subp.add_parser("gencfg", formatter_class=fmtcls,
                             help="generate full config from parts")
    gencfg.add_argument("-o", "--output", required=True, help="output file")
    gencfg.add_argument("-c", "--config", help="full training configuration")
    gencfg.add_argument("-d", "--data", help="training strategy and data")
    gencfg.add_argument("-m", "--model", help="specification of the model")
    gencfg.add_argument("-s", "--seeds", help="seed config for initializing RNGs")
    gencfg.add_argument("-i", "--inspect", help="specification of metrics")
    gencfg.add_argument("-e", "--env", "--environment", dest="env",
                       help="environment config")

    args = parser.parse_args()

    # persistent compile cache + AOT program store: configured after
    # parsing (JAX_COMPILATION_CACHE_DIR wins over --compile-cache over
    # RMD_COMPILE_CACHE over the default) but before any backend use
    import os

    from . import compile as programs
    from .utils.compcache import enable_persistent_cache

    if getattr(args, "compile_cache", None):
        # export so lower-precedence config (the env file's 'compile'
        # section) can see the flag won
        os.environ["RMD_COMPILE_CACHE"] = args.compile_cache
    enable_persistent_cache(getattr(args, "compile_cache", None))
    programs.enable_aot()

    commands = {
        "checkpoint": cmd.checkpoint,
        "evaluate": cmd.evaluate,
        "e": cmd.evaluate,
        "eval": cmd.evaluate,
        "gencfg": cmd.generate_config,
        "serve": cmd.serve,
        "train": cmd.train,
        "t": cmd.train,
    }

    if args.command is None:
        parser.print_help()
        return

    commands[args.command](args)
