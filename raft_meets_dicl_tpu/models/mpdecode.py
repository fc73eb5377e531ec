"""Multiprocess sample decode with shared-memory array transport.

The thread-pooled loader overlaps I/O and the GIL-releasing parts of
cv2/numpy, but the pure-Python decode path (dataset indexing, augmentation
glue, per-sample validation) stays single-core. This pool forks worker
processes that run ``source[index]`` and hand the resulting arrays back
through POSIX shared memory — one segment per sample, written once by the
worker, read zero-copy by the consumer (``collate`` copies each sample
once, into the batch's arrays, and that is the single copy),
then unlinked. Only the metadata list travels through the result queue's
pickle channel.

Fork start method by default (the source pipeline is inherited, nothing
is pickled); override with ``RMD_LOADER_MP=spawn`` for sources that hold
fork-unsafe state. Workers never touch jax.

Self-healing: ``result()`` polls the queue with a timeout instead of
blocking forever, so a worker that died (OOM-killed, segfaulted in a
native decode, fault-injected) is detected, respawned with backoff, and
its lost in-flight work resubmitted — bounded by ``RMD_LOADER_RESPAWNS``
(then the pool gives up loudly). ``RMD_LOADER_TIMEOUT`` bounds the total
wait per sample so a wedged-but-alive worker can't hang the run.
"""

import multiprocessing as mp
import os
import pickle
import queue as _queue
import time
from multiprocessing import shared_memory

import numpy as np

from ..testing import faults
from ..utils import env


class PoolBroken(RuntimeError):
    """The decode pool itself is unusable (respawn budget exhausted) —
    not a per-sample failure, so the loader's retry path must not
    swallow it."""


def _unregister_tracker(name):
    """Detach a segment from the creating process's resource tracker.

    SharedMemory(create=True) registers with the *worker's* tracker; the
    consumer unlinks explicitly, so tracker cleanup at worker exit would
    only race it and log spurious leak warnings.
    """
    try:
        from multiprocessing import resource_tracker

        resource_tracker.unregister("/" + name.lstrip("/"), "shared_memory")
    except Exception:  # noqa: BLE001 - tracker APIs are version-dependent
        pass


def encode_sample(sample):
    """Sample → (shm_name, array descriptors, meta); arrays in one segment."""
    img1, img2, flow, valid, meta = sample
    arrays = [img1, img2, flow, valid]
    total = sum(a.nbytes for a in arrays if a is not None)
    shm = shared_memory.SharedMemory(create=True, size=max(1, total))
    _unregister_tracker(shm.name)

    descr = []
    offset = 0
    for a in arrays:
        if a is None:
            descr.append(None)
            continue
        a = np.ascontiguousarray(a)
        dst = np.ndarray(a.shape, a.dtype, buffer=shm.buf, offset=offset)
        dst[...] = a
        descr.append((offset, a.shape, a.dtype))
        offset += a.nbytes

    name = shm.name
    shm.close()
    return name, descr, meta


def decode_sample(payload):
    """Payload → ((img1, img2, flow, valid, meta), shm handle).

    The arrays are views into the segment: the caller must keep ``shm``
    open until it has copied them out (collate does, once a sample), then
    ``shm.close(); shm.unlink()``.
    """
    name, descr, meta = payload
    shm = shared_memory.SharedMemory(name=name)
    arrays = []
    for d in descr:
        if d is None:
            arrays.append(None)
            continue
        offset, shape, dtype = d
        arrays.append(np.ndarray(shape, dtype, buffer=shm.buf, offset=offset))
    img1, img2, flow, valid = arrays
    return (img1, img2, flow, valid, meta), shm


def _discard_payload(payload):
    """Unlink a result segment the consumer will never read."""
    if payload is None:
        return
    try:
        shm = shared_memory.SharedMemory(name=payload[0])
        shm.close()
        shm.unlink()
    except Exception:  # noqa: BLE001 - best-effort cleanup
        pass


def _worker(source, tasks, results):
    while True:
        task = tasks.get()
        if task is None:
            return
        seq, index = task
        try:
            if faults.fire("kill_worker", index=index) is not None:
                os._exit(17)  # injected hard death: no result, no cleanup
            results.put((seq, encode_sample(source[index]), None))
        except BaseException as e:  # noqa: BLE001 - re-raised by consumer
            try:
                pickle.dumps(e)
            except Exception:  # noqa: BLE001
                e = RuntimeError(f"{type(e).__name__}: {e}")
            results.put((seq, None, e))


class DecodePool:
    """Fixed pool of decode processes with in-order result retrieval.

    Dead workers are respawned (with backoff) and their lost in-flight
    tasks resubmitted; duplicate results from a resubmission race are
    detected by sequence number and their segments discarded.
    """

    def __init__(self, source, procs, start_method=None,
                 timeout=None, poll=None, max_respawns=None):
        method = start_method or env.get_str("RMD_LOADER_MP")
        self._ctx = mp.get_context(method)
        self._source = source
        self._tasks = self._ctx.Queue()
        self._results = self._ctx.Queue()
        self._received = {}
        self._inflight = {}   # seq -> index, until the result is received
        self._done = set()    # delivered seqs (duplicate-result guard)
        self._seq = 0
        self._respawns = 0
        self._backoff = 0.0

        # total wait per sample before the pool declares the pipeline
        # wedged; poll interval bounds dead-worker detection latency
        self._timeout = (timeout if timeout is not None
                         else env.get_float("RMD_LOADER_TIMEOUT"))
        self._poll = (poll if poll is not None
                      else env.get_float("RMD_LOADER_POLL"))
        self._max_respawns = int(max_respawns if max_respawns is not None
                                 else env.get_int("RMD_LOADER_RESPAWNS"))

        self._workers = [self._spawn() for _ in range(max(1, int(procs)))]

    def _spawn(self):
        w = self._ctx.Process(
            target=_worker, args=(self._source, self._tasks, self._results),
            daemon=True)
        w.start()
        return w

    def submit(self, index):
        """Queue one sample decode; returns its sequence token."""
        seq = self._seq
        self._seq += 1
        self._inflight[seq] = int(index)
        self._tasks.put((seq, int(index)))
        return seq

    def _heal(self):
        """Respawn dead workers and resubmit their lost in-flight tasks.

        A worker that died mid-decode took its task with it; since the
        queue doesn't say which, every unreceived in-flight task is
        resubmitted — tasks that were merely queued get decoded twice,
        and the duplicate result is dropped by sequence number.
        """
        from .. import telemetry, utils

        dead = [(i, w) for i, w in enumerate(self._workers)
                if not w.is_alive()]
        if not dead:
            return

        log = utils.logging.Logger("data:mpdecode")
        for i, w in dead:
            self._respawns += 1
            if self._respawns > self._max_respawns:
                raise PoolBroken(
                    f"decode worker died (exit code {w.exitcode}) and the "
                    f"respawn budget ({self._max_respawns}) is exhausted — "
                    "the input pipeline is persistently failing")
            log.warn(
                f"decode worker {i} died (exit code {w.exitcode}): "
                f"respawning ({self._respawns}/{self._max_respawns})")
            telemetry.get().emit(
                "respawn", worker=i, exitcode=w.exitcode,
                respawns=self._respawns)
            if self._backoff:
                time.sleep(self._backoff)
            self._backoff = min(max(0.1, self._backoff * 2), 10.0)
            self._workers[i] = self._spawn()

        for seq, index in list(self._inflight.items()):
            if seq not in self._received:
                self._tasks.put((seq, index))

    def result(self, seq):
        """Block until sample ``seq`` is decoded; returns (sample, shm)."""
        deadline = time.monotonic() + self._timeout
        while seq not in self._received:
            try:
                s, payload, err = self._results.get(
                    timeout=max(0.01, self._poll))
            except _queue.Empty:
                self._heal()
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"decode pool produced no result for "
                        f"{self._timeout:.0f}s (sample seq {seq}) — input "
                        "pipeline wedged") from None
                continue
            if s in self._done or s in self._received:
                # duplicate from a resubmission race: keep the first
                _discard_payload(payload)
                continue
            self._received[s] = (payload, err)
            self._inflight.pop(s, None)
        payload, err = self._received.pop(seq)
        self._done.add(seq)
        if err is not None:
            raise err
        return decode_sample(payload)

    def shutdown(self):
        for _ in self._workers:
            self._tasks.put(None)
        for w in self._workers:
            w.join(timeout=5)
            if w.is_alive():
                w.terminate()
        # drop any undelivered segments (consumer bailed mid-epoch)
        for payload, err in self._received.values():
            _discard_payload(payload)
        self._received.clear()
        self._inflight.clear()
        while True:
            try:
                s, payload, err = self._results.get_nowait()
            except Exception:  # noqa: BLE001 - queue empty
                break
            _discard_payload(payload)
