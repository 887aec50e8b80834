"""What JAX reports of the machine a run is on, and the wait for it."""

import jax


class NoAccelerator(RuntimeError):
    pass


def require(platform: str, chips: int) -> list:
    """The first `chips` devices, or NoAccelerator when the default backend
    is another platform or holds fewer.  JAX's own answer to a libtpu that
    fails to start is to warn and go on with the CPU, so this is asked
    before any work."""
    devices = jax.devices()
    if devices[0].platform != platform:
        raise NoAccelerator(f"JAX's platform is {devices[0].platform!r}, "
                            f"not {platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips and JAX finds "
                            f"{len(devices)}")
    return devices[:chips]


def sync() -> None:
    """Wait until the device has finished everything enqueued so far.

    Blocks on every live array rather than on one the program is known to
    keep: the benchmark then needs no name from inside the program, and an
    output of the last program enqueued is always among them.  An array
    donated to a later program is skipped; every other error, a device
    failure that only shows at the wait among them, is the caller's.
    """
    for a in jax.live_arrays():
        if a.is_deleted():
            continue
        try:
            a.block_until_ready()
        except RuntimeError:
            if a.is_deleted():  # donated between the check and the wait
                continue
            raise


def peaks_by_device(devices) -> list:
    """`peak_bytes_in_use` of each of `devices`, 0 where the backend
    reports none (the CPU)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks_by_device(devices))}
