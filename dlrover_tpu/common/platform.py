"""Platform pinning helpers.

Sharding logic (tests, dry runs) is validated on the host backend with N
virtual CPU devices (``--xla_force_host_platform_device_count``), mirroring
the reference's multi-node-without-cluster trick (SURVEY.md §4);
:func:`force_virtual_cpu` pins that, and must run before any backend
initialization because jax refuses platform changes afterwards.

The other direction is :func:`pin_accelerator`: a process that was
started for the TPU pins ``tpu``, so that a failed TPU initialization
raises instead of JAX warning and carrying on on the CPU.
"""

import os
import re

_COUNT_FLAG = "xla_force_host_platform_device_count"


def force_virtual_cpu(n_devices: int, platform: str = "cpu") -> None:
    """Pin JAX to ``platform`` with >= ``n_devices`` host devices.

    Must be called before the first JAX backend initialization; afterwards
    it is a best-effort no-op (jax refuses platform changes post-init).
    """
    os.environ["JAX_PLATFORMS"] = platform
    if "cpu" in platform:
        flags = os.environ.get("XLA_FLAGS", "")
        match = re.search(rf"--{_COUNT_FLAG}=(\d+)", flags)
        if match is None:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --{_COUNT_FLAG}={n_devices}"
            ).strip()
        elif int(match.group(1)) < n_devices:
            os.environ["XLA_FLAGS"] = flags.replace(
                match.group(0), f"--{_COUNT_FLAG}={n_devices}"
            )

    import jax

    try:
        jax.config.update("jax_platforms", platform)
    except RuntimeError:
        pass  # backend already initialized; caller's device assert decides


def pin_accelerator(platform: str = "tpu") -> str:
    """Pin JAX to ``platform`` unless the caller's environment already
    pinned one (``JAX_PLATFORMS=cpu`` for tests and rehearsals is the
    caller's own choice and is honored). Returns the pin in effect.

    With the pin, ``jax.devices()`` raises when the accelerator cannot
    be initialized (another process holds the chip, a broken plugin) —
    without it JAX falls back to the CPU and the program looks healthy.
    Call before the first backend initialization.
    """
    pinned = os.environ.get("JAX_PLATFORMS")
    if pinned:
        return pinned
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)
    return platform


def device_summary() -> dict:
    """The devices as THIS process sees them: the triple every record,
    health page and smoke line names, so that no reader has to guess
    whether an answer came from the chip or from the host."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def routable_host(override_env: str = "") -> str:
    """Best non-loopback IP for cross-host env exports.

    ``gethostbyname(gethostname())`` resolves to 127.0.1.1 on stock
    Debian/Ubuntu hosts files, which silently breaks any service whose
    address is handed to OTHER hosts (they dial their own loopback).
    Resolution order: the ``override_env`` env var when the caller
    names one (only for addresses that genuinely are per-deployment,
    e.g. the master's — a per-node endpoint must NOT honor a
    job-uniform override or every node advertises the same address) →
    the hostname's first A record when non-loopback (the resolved IP
    is returned, not the name: peers on bare-metal clusters without
    shared DNS can route an IP but not resolve a foreign hostname) →
    outbound-interface IP via the UDP-connect trick (no packet is
    sent) → loopback as a last resort (isolated test machines).
    """
    import socket

    if override_env:
        override = os.getenv(override_env, "")
        if override:
            return override
    try:
        infos = socket.getaddrinfo(socket.gethostname(), None, socket.AF_INET)
        if infos and not infos[0][4][0].startswith("127."):
            return infos[0][4][0]
    except OSError:
        pass
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            # connect() on a datagram socket sends nothing; it only
            # resolves the outbound interface for the default route.
            s.connect(("8.8.8.8", 80))
            ip = s.getsockname()[0]
        finally:
            s.close()
        if not ip.startswith("127."):
            return ip
    except OSError:
        pass
    return "127.0.0.1"
