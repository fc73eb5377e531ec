"""The machine a run is on: the chips it must have, what the device's
memory peaked at, how the program's executables are let go."""


class NoAccelerator(SystemExit):
    pass


def require(chips, platform="tpu"):
    """The devices of the run, or no run: ``platform`` must be present
    with exactly ``chips`` devices, the machine the cell was sized for.
    There is no fallback."""
    import jax

    try:
        devices = jax.devices(platform)
    except RuntimeError as e:
        raise NoAccelerator(f"no '{platform}' platform: {e}") from e
    if len(devices) != chips:
        raise NoAccelerator(
            f"cell needs {chips} {platform} devices, found {len(devices)}")
    return devices


def describe(devices):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices):
    """Peak on the fullest chip. The TPU runtime counts a loaded program's
    temporaries as ``*_reserved`` and leaves them out of ``*_in_use`` (PR
    21 measured it), so the two peaks add up to what the chip held."""
    peaks = []
    for d in devices:
        s = d.memory_stats() or {}
        if "peak_bytes_in_use" in s:
            peaks.append(int(s["peak_bytes_in_use"])
                         + int(s.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else 0


def release_programs():
    """Drop the program's compiled executables and whatever arrays only
    they kept alive, and say what the devices still hold."""
    import gc

    import jax
    from raft_meets_dicl_tpu import compile as programs

    programs.registry().clear()
    jax.clear_caches()
    gc.collect()
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        if s:
            print(f"[memory] {d} after release: in_use "
                  f"{s.get('bytes_in_use', 0) / 2**30:.2f} GiB, reserved "
                  f"{s.get('bytes_reserved', 0) / 2**30:.2f} GiB, peak "
                  f"{s.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB + "
                  f"{s.get('peak_bytes_reserved', 0) / 2**30:.2f} GiB reserved",
                  flush=True)
