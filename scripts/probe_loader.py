#!/usr/bin/env python3
"""What the host can feed: a train cell's loader alone, no accelerator.

    python3 scripts/probe_loader.py [--workload raft-train-things-dp4]
        [--seconds 10] [--workers 16 ...] [--tree DIR]

Builds the cell's input pipeline as the program does (the synthetic source
of ``benchmark/harness/train.py``, the model's input spec, the wire format
and the loader arguments of the cell's environment), pulls batches for
``--seconds`` and prints, a line of JSON a worker count, the pairs a second
it delivered, the CPU-seconds the process burnt a second of wall
(``os.times()``: every thread's, the kernel's share apart), beside the CPUs
the machine gives the process, and the pulling thread's share
(``puller_busy_pct``: this thread's own CPU time, ``time.thread_time()``,
over the wall: what the puller does to a batch itself, its waits for the
workers left out); last, what ``collate`` of one batch costs with the
workers gone (the serial assembly: allocate once, one copy a sample). A
cell's rate cannot pass the first number; where the second stands at the
CPUs available the host's cores are what holds it, and where it stands far
under them the workers wait on each other or on the puller, which the
third tells apart: near 100 the puller is the loader's period (PR 40's
tree), near 0 it only waits (the workers place their samples themselves).

``--stacks`` samples every thread's Python stack through the window and
prints where the threads sit, in threads: sixteen workers idle in the pool's
``_worker`` mean the consumer (the pulling thread, or whoever takes its
batches) is what the workers wait for, not each other.

``--tree`` runs another checkout's program and benchmark (the parent's,
unpacked by ``git archive``) from this one script. The process is held to
the CPU: run it before anything touches the chips.
"""

import argparse
import collections
import json
import os
import resource
import statistics
import sys
import threading
import time
from pathlib import Path

import numpy as np


class _Sampler:
    """Every 5 ms, each thread's innermost frame inside the program."""

    def __init__(self, tree):
        self.tree = str(Path(tree).resolve())
        self.seen, self.rounds, self.stop = collections.Counter(), 0, False
        self.puller = threading.get_ident()
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self):
        me = threading.get_ident()
        while not self.stop:
            time.sleep(0.005)
            for tid, frame in sys._current_frames().items():
                if tid == me:
                    continue
                inner = frame.f_code.co_name
                while frame is not None and not (
                        frame.f_code.co_filename.startswith(self.tree)
                        and "raft_meets_dicl_tpu" in frame.f_code.co_filename):
                    frame = frame.f_back
                where = inner if frame is None else "%s:%s:%d" % (
                    Path(frame.f_code.co_filename).name,
                    frame.f_code.co_name, frame.f_lineno)
                self.seen[("puller " if tid == self.puller else "") + where] += 1
            self.rounds += 1

    def report(self):
        self.stop = True
        for where, n in self.seen.most_common(12):
            print(f"  {n / max(self.rounds, 1):6.2f} threads at {where}",
                  flush=True)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", default="raft-train-things-dp4")
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--workers", type=int, nargs="*", default=None,
                        help="worker counts to try (default: the cell's)")
    parser.add_argument("--stacks", action="store_true",
                        help="sample the threads' stacks, print where they sit")
    parser.add_argument("--tree", default=str(Path(__file__).resolve().parents[1]))
    args = parser.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, str(Path(args.tree).resolve()))

    from benchmark.harness import spec
    from benchmark.harness.train import ROOT, _stage_config
    from raft_meets_dicl_tpu import models, strategy
    from raft_meets_dicl_tpu.cmd.train import Environment
    from raft_meets_dicl_tpu.models.wire import WireFormat

    cell = spec.load_cell(args.workload)
    env = Environment.load(cell.config["env"])
    wire = WireFormat.from_config(env.wire)
    strat_cfg, batch = _stage_config(cell)
    stage = strategy.load(ROOT, strat_cfg).stages[0]
    model = models.load(cell.config["model"])
    adapter = model.input.apply(stage.data.source,
                                normalize=wire is None).jax(wire=wire)
    loader_args = dict(env.loader_args)

    print(json.dumps({
        "tree": str(ROOT), "workload": cell.name, "batch": batch,
        "source": stage.data.source.description(),
        "wire": None if wire is None else wire.describe(),
        "loader_args": loader_args, "os.cpu_count": os.cpu_count(),
        "sched_getaffinity": len(os.sched_getaffinity(0))}), flush=True)

    for workers in args.workers or [loader_args.get("num_workers", 4)]:
        loader = adapter.loader(
            batch_size=batch, shuffle=stage.data.shuffle,
            drop_last=stage.data.drop_last,
            **dict(loader_args, num_workers=workers, seed=1))
        batches = iter(loader)
        next(batches)                       # the render's compile, the pool
        sampler = _Sampler(args.tree) if args.stacks else None
        wall0, cpu0, own0 = time.perf_counter(), os.times(), time.thread_time()
        pairs, fetch = 0, []
        while time.perf_counter() - wall0 < args.seconds:
            *_arrays, meta = next(batches)
            pairs += len(meta)
            fetch += [m.fetch_s for m in meta
                      if getattr(m, "fetch_s", None) is not None]
        wall, cpu1 = time.perf_counter() - wall0, os.times()
        own = time.thread_time() - own0
        user, system = cpu1.user - cpu0.user, cpu1.system - cpu0.system
        print(json.dumps({
            "workers": workers, "pairs_per_s": round(pairs / wall, 2),
            "cpu_s_per_s": round((user + system) / wall, 2),
            "of_it_system": round(system / wall, 2),
            "puller_busy_pct": round(100.0 * own / wall, 1),
            "cpu_s_per_pair": round((user + system) / max(pairs, 1), 4),
            "fetch_ms": round(1e3 * sum(fetch) / len(fetch), 2)
            if fetch else None,
            "pairs": pairs, "wall_s": round(wall, 2)}), flush=True)
        if sampler is not None:
            sampler.report()
        batches.close()

    # a batch assembled by one thread, with every worker gone: what
    # ``collate`` (the serial assembly) costs when nothing else touches
    # memory or the interpreter
    from raft_meets_dicl_tpu.models.input import collate

    samples = [adapter[i] for i in range(batch)]
    rng, alone, faults = np.random.default_rng(1), [], []
    for _ in range(5):
        t0, f0 = time.perf_counter(), resource.getrusage(
            resource.RUSAGE_SELF).ru_minflt
        collate(samples, stage.data.shuffle, rng)
        alone.append(1e3 * (time.perf_counter() - t0))
        faults.append(resource.getrusage(resource.RUSAGE_SELF).ru_minflt - f0)
    print(json.dumps({"collate_alone_ms": round(statistics.median(alone), 2),
                      "minor_faults_a_call": int(statistics.median(faults)),
                      "calls_ms": [round(a, 1) for a in alone]}), flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
