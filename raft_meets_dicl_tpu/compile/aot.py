"""AOT program artifacts: serialized compiled executables on disk.

The persistent XLA cache (utils.compcache) removes the *compile* cost of
a repeat boot but still pays tracing + cache lookup per program; this
layer removes the whole warmup. A compiled step is serialized via
``jax.experimental.serialize_executable`` into a content-addressed file
under the ``programs/`` directory — keyed by the ProgramKey digest plus
the concrete input shape signature — and a later boot (same config, same
topology) deserializes it directly: zero traces, zero backend compiles.
That is what makes fleet-style replicas cheap: compile once, ship the
artifact (ROADMAP item 1), and what makes a resumed run start stepping
immediately (item 3's warmup budget).

Everything here is best-effort: a missing, corrupt, or version-mismatched
artifact degrades to the normal JIT path (the registry records the
fallback in telemetry), never to an error.
"""

import hashlib
import io
import os
import pickle
import time
import zlib

from ..utils import env

_MAGIC = "RMDP1"
# bump to invalidate every existing artifact when the program contract
# changes (arg order, aux layout, ...); 3: the record holds the program's
# trace-time counts, and the version is part of the artifact's name, so
# that two layouts share a store without overwriting each other
_LAYOUT_VERSION = 3

_state = {"on": False, "dir": None}


def default_dir():
    """``programs/`` next to the persistent compile cache."""
    from ..utils import compcache

    base = compcache.effective_dir() or compcache.DEFAULT_DIR
    return os.path.join(base, "programs")


def enable_aot(path=None):
    """Turn the AOT program store on (CLI boots call this, mirroring
    ``compcache.enable_persistent_cache``); ``RMD_AOT=0`` wins. Returns
    the effective programs directory, or None when disabled."""
    if not env.get_bool("RMD_AOT"):
        _state["on"] = False
        return None
    _state["on"] = True
    _state["dir"] = path or env.raw("RMD_AOT_DIR") or None
    return programs_dir()


def disable_aot():
    _state["on"] = False


def aot_enabled():
    return _state["on"]


def programs_dir():
    return _state["dir"] or default_dir()


_fingerprint = None


def fingerprint():
    """Version string an artifact must match to be loadable: jax/jaxlib,
    the artifact layout version, and the backend topology (a serialized
    executable references concrete devices)."""
    global _fingerprint
    if _fingerprint is None:
        import jax
        import jaxlib

        dev = jax.devices()[0]
        _fingerprint = (
            f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
            f"layout={_LAYOUT_VERSION} "
            f"backend={dev.platform}:{getattr(dev, 'device_kind', '?')} "
            f"n={jax.device_count()}")
    return _fingerprint


def artifact_path(key, sig):
    digest = hashlib.sha256(
        (f"layout={_LAYOUT_VERSION}\0" + key.canonical() + "\0"
         + repr(sig)).encode()).hexdigest()
    return os.path.join(programs_dir(), f"{digest}.rmdp")


def tombstone(path):
    """Mark a (key, sig) as not AOT-loadable under the current
    fingerprint: some executables serialize but fail to load back (e.g.
    XLA-CPU fusions with unexported symbols). The marker suppresses
    save/fail churn on every later boot — the program just runs through
    the normal JIT path (+ persistent compile cache). A jax/backend
    upgrade changes the fingerprint and retries."""
    try:
        with open(path + ".noaot", "w") as fd:
            fd.write(fingerprint() + "\n")
    except OSError:
        pass


def tombstoned(path):
    try:
        with open(path + ".noaot") as fd:
            return fd.readline().strip() == fingerprint()
    except OSError:
        return False


def _validate_artifact(path):
    """Cheap record validation without deserializing the executable:
    magic, runtime-fingerprint match (same jax/backend/topology), CRC.
    Returns (ok, reason)."""
    try:
        with open(path, "rb") as fd:
            record = pickle.loads(fd.read())
    except Exception as e:  # noqa: BLE001 - any decode failure
        return False, f"unpickle: {type(e).__name__}"
    if not isinstance(record, dict) or record.get("magic") != _MAGIC:
        return False, "bad magic"
    if record.get("fingerprint") != fingerprint():
        return False, (f"fingerprint '{record.get('fingerprint')}' vs "
                       f"runtime '{fingerprint()}'")
    payload = record.get("payload")
    if payload is None or zlib.crc32(payload) != record.get("crc"):
        return False, "crc mismatch"
    return True, "ok"


def _copy_artifacts(src, dest, event):
    """Validated artifact transfer between program stores (the fleet
    distribution primitive): every ``*.rmdp`` whose record passes
    :func:`_validate_artifact` is copied atomically; invalid or
    version-mismatched artifacts are skipped (never raising), existing
    destination files are left alone (content-addressed names — same
    name means same program). Tombstones stay local: they record a
    host-specific load failure, not a property of the artifact.
    Returns ``{copied, present, invalid, artifacts}``.
    """
    import glob as _glob

    from .. import telemetry

    os.makedirs(dest, exist_ok=True)
    copied, present, invalid = [], 0, {}
    for path in sorted(_glob.glob(os.path.join(src, "*.rmdp"))):
        name = os.path.basename(path)
        target = os.path.join(dest, name)
        if os.path.exists(target):
            present += 1
            continue
        ok, reason = _validate_artifact(path)
        if not ok:
            invalid[name] = reason
            continue
        tmp = f"{target}.tmp.{os.getpid()}"
        with open(path, "rb") as sfd, open(tmp, "wb") as dfd:
            dfd.write(sfd.read())
        os.replace(tmp, target)
        copied.append(name)
    out = {"copied": len(copied), "present": present,
           "invalid": len(invalid), "artifacts": copied}
    telemetry.get().emit("aot", event=event, src=str(src), dest=str(dest),
                         **{k: out[k] for k in
                            ("copied", "present", "invalid")})
    return out


def publish(dest, src=None):
    """Publish the local program store into a shared fleet store: one
    ``serve --prebuild`` host exports its compiled executables, every
    replica fetches them. Only artifacts matching the *current* runtime
    fingerprint travel — that is the same-topology portability check."""
    return _copy_artifacts(src or programs_dir(), dest, "publish")


def fetch(src, dest=None):
    """Pull published artifacts into the local program store (replica
    boot): validated against the local runtime fingerprint, so an
    artifact built on a different jax/backend/topology is skipped and
    that program simply JIT-compiles."""
    return _copy_artifacts(src, dest or programs_dir(), "fetch")


def save(path, key, sig, compiled, trace_counts=None, text_facts=None):
    """Serialize ``compiled`` (a jax.stages.Compiled) to ``path``
    atomically, with the counts its trace noted (``telemetry.note_trace``:
    a boot that loads the executable never traces, and reads them from
    here) and what the saving boot read off its compiled text
    (``text_facts``: ``mosaic_calls`` and the ``owners`` record; a boot
    that loads the executable need not take a text of 7-16 MB to say them
    again). Returns (nbytes, seconds); raises on failure — callers treat
    a failed save as cosmetic."""
    from jax.experimental import serialize_executable

    t0 = time.perf_counter()
    payload, in_tree, out_tree = serialize_executable.serialize(compiled)
    record = {
        "magic": _MAGIC,
        "fingerprint": fingerprint(),
        "key": key.canonical(),
        "sig": repr(sig),
        "crc": zlib.crc32(payload),
        "payload": payload,
        # the executable's device assignment, in order: loading needs it
        # (the default is every device of the backend, which is wrong
        # for a one-device program on a multi-device host)
        "devices": [d.id for d in
                    compiled.runtime_executable().local_devices()],
        "in_tree": in_tree,
        "out_tree": out_tree,
        "trace_counts": dict(trace_counts or {}),
        # additive: an artifact without it loads as before
        "text_facts": text_facts,
    }
    buf = io.BytesIO()
    pickle.dump(record, buf, protocol=pickle.HIGHEST_PROTOCOL)
    data = buf.getvalue()

    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as fd:
        fd.write(data)
    os.replace(tmp, path)
    return len(data), time.perf_counter() - t0


def load(path, key, sig):
    """Deserialize an artifact back into a callable Compiled.

    Returns ``(compiled, status, info)`` where status is one of
    ``hit`` (compiled is live), ``missing``, ``corrupt``, ``version``
    (fingerprint mismatch — stale jax/backend), or ``error``; ``info``
    carries {bytes, seconds, trace_counts, text_facts} on a hit and a
    reason string otherwise.
    Never raises.
    """
    t0 = time.perf_counter()
    try:
        try:
            with open(path, "rb") as fd:
                data = fd.read()
        except FileNotFoundError:
            return None, "missing", "no artifact"

        try:
            record = pickle.loads(data)
        except Exception as e:  # noqa: BLE001 - any decode failure
            return None, "corrupt", f"unpickle: {type(e).__name__}"

        if not isinstance(record, dict) or record.get("magic") != _MAGIC:
            return None, "corrupt", "bad magic"
        if record.get("fingerprint") != fingerprint():
            return None, "version", (
                f"artifact '{record.get('fingerprint')}' vs "
                f"runtime '{fingerprint()}'")
        if record.get("key") != key.canonical() or record.get("sig") != repr(sig):
            # hash collision or a hand-moved file: treat as absent
            return None, "corrupt", "key mismatch"
        payload = record["payload"]
        if zlib.crc32(payload) != record.get("crc"):
            return None, "corrupt", "crc mismatch"

        import jax
        from jax.experimental import serialize_executable

        by_id = {d.id: d for d in jax.devices()}
        compiled = serialize_executable.deserialize_and_load(
            payload, record["in_tree"], record["out_tree"],
            execution_devices=[by_id[i] for i in record["devices"]])
        return compiled, "hit", {
            "bytes": len(data),
            "seconds": time.perf_counter() - t0,
            "trace_counts": dict(record.get("trace_counts") or {}),
            "text_facts": record.get("text_facts"),
        }
    except Exception as e:  # noqa: BLE001 - artifacts must never break boot
        return None, "error", f"{type(e).__name__}: {str(e)[:160]}"
