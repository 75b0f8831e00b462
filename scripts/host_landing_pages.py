"""Whose pages a flash save's device-to-host copies land in.

    python scripts/host_landing_pages.py [--rounds 6] [--only NAME,...]
        [--setting NAME=TUNABLES ...] [--out FILE]

The flash save (``checkpoint/shm_handler.py: _plan``) kicks one
``copy_to_host_async`` a leaf and then waits for each host copy. A kick
allocates the host array its copy lands in; whether that array is made of
pages the process already has depends on what glibc did when the last
save's host copies were freed. For each setting, in a fresh process (a
chip has one owner: this parent never imports JAX), this builds a state
of GPT-2 small's leaves on the device (375 leaves, 1,493,268,492 bytes:
params and the two Adam moments) and does ``--rounds`` rounds of kick all,
wait for all, drop. A round's line says: seconds in the kicks and in the
waits, the process's minor faults (``ru_minflt``) over both, where malloc
holds the copies while they live (``malloc_info``: the main arena's heap,
the other arenas' heaps, mapped chunks) and the process's resident set
after the drop, with the largest it has been. Which arena holds the bytes says which thread
asked for them: the main thread allocates from the main arena, every
other thread from an arena of its own. The last line is ``{"summary":
...}``: a setting's medians over the rounds after its first.

A setting is a ``GLIBC_TUNABLES`` value for the child (``glibc.malloc.``
is put before each key); ``parent`` is the allocator as the parent commit
runs it. The numbers are wanted from the chip's host: ``chiprun -- python
scripts/host_landing_pages.py``; off the chip the "device" is the CPU
backend, whose host copies are views of the device's own buffers, so only
the plumbing is rehearsed there.
"""

import argparse
import ctypes
import gc
import json
import os
import resource
import statistics
import subprocess
import sys
import time
import xml.etree.ElementTree as ET

NEVER = str(2**64 - 1)  # size_t's largest: a threshold that is never reached

SETTINGS = {
    "parent": "",
    "trim_never": f"trim_threshold={NEVER}",
    "mmap_max_0": "mmap_max=0",
    "trim_never+mmap_threshold_32m": f"trim_threshold={NEVER}:mmap_threshold=33554432",
    "trim_never+mmap_max_0": f"trim_threshold={NEVER}:mmap_max=0",
    "trim_never+mmap_max_0+arena_max_1": f"trim_threshold={NEVER}:mmap_max=0:arena_max=1",
    "top_pad_1800m": "top_pad=1800000000",  # PR 26's experiment, sized to this state
}

# GPT-2 small's train state: (shape, how many) in each of params, mu, nu.
LEAVES = [
    ((50304, 768), 1), ((1024, 768), 1), ((768, 3, 12, 64), 12), ((12, 64, 768), 12),
    ((768, 3072), 12), ((3072, 768), 12), ((3072,), 12), ((768,), 62),
]


def tunables(setting: str) -> str:
    return ":".join(f"glibc.malloc.{kv}" for kv in setting.split(":") if kv)


def rss_mb() -> float:
    """This process's resident set (``/proc/self/statm``), in MB."""
    pages = int(open("/proc/self/statm").read().split()[1])
    return round(pages * resource.getpagesize() / 1e6, 1)


def peak_rss_mb() -> float:
    """The largest the resident set has been (``ru_maxrss``, which is
    ``VmHWM``), in MB; 0 where the kernel does not keep it."""
    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e6, 1)


def huge_pages() -> str:
    try:
        return open("/sys/kernel/mm/transparent_hugepage/enabled").read().strip()
    except OSError:
        return "none"


def minor_faults() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


def malloc_holds(libc) -> dict:
    """MB that malloc has from the system: the main arena's heap, the
    other arenas' heaps (and how many there are), mapped chunks."""
    buf, size = ctypes.c_void_p(), ctypes.c_size_t()
    fp = libc.open_memstream(ctypes.byref(buf), ctypes.byref(size))
    libc.malloc_info(0, ctypes.c_void_p(fp))
    libc.fclose(ctypes.c_void_p(fp))
    root = ET.fromstring(ctypes.string_at(buf, size.value))
    libc.free(buf)
    heaps = [int(h.find("system[@type='current']").get("size")) for h in root.findall("heap")]
    mapped = root.find("total[@type='mmap']")
    return dict(
        main_mb=round(heaps[0] / 1e6, 1), arenas_mb=round(sum(heaps[1:]) / 1e6, 1),
        arenas=len(heaps) - 1, mapped_mb=round(int(mapped.get("size")) / 1e6, 1),
        mapped_chunks=int(mapped.get("count")),
    )


def child(rounds: int) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    libc = ctypes.CDLL(None)
    libc.open_memstream.restype = ctypes.c_void_p

    def say(**row):
        print(json.dumps(row), flush=True)

    dev = jax.devices()[0]
    say(platform=dev.platform, kind=dev.device_kind, jax=jax.__version__,
        numpy=np.__version__, glibc_tunables=os.environ.get("GLIBC_TUNABLES", ""),
        thp=huge_pages())

    @jax.jit
    def build():
        shapes = [shape for shape, n in LEAVES for _ in range(n)] * 3
        # a value a leaf, so that no two leaves are one operation to the compiler
        return [jnp.full(shape, float(k), jnp.float32) for k, shape in enumerate(shapes)] + [
            jnp.zeros((), jnp.int32)] * 3

    # What a train step does to the loop's state: new arrays in the old
    # ones' device memory; the old arrays, and their host copies, go.
    bump = jax.jit(lambda s: jax.tree.map(lambda x: x + jnp.ones((), x.dtype), s),
                   donate_argnums=0)
    state = jax.block_until_ready(build())
    leaves = jax.tree.leaves(state)
    say(leaves=len(leaves), state_bytes=sum(l.size * l.dtype.itemsize for l in leaves),
        rss_mb=rss_mb(), peak_rss_mb=peak_rss_mb())
    for i in range(rounds):
        leaves = jax.tree.leaves(state)
        f0, t0 = minor_faults(), time.perf_counter()
        for leaf in leaves:
            leaf.copy_to_host_async()
        t1 = time.perf_counter()
        held = [np.asarray(leaf) for leaf in leaves]
        t2, f1 = time.perf_counter(), minor_faults()
        holds = malloc_holds(libc)
        del held, leaves, leaf
        state = jax.block_until_ready(bump(state))
        gc.collect()
        say(round=i, kick_s=round(t1 - t0, 4), wait_s=round(t2 - t1, 4),
            minor_faults=f1 - f0, held=holds, after_drop=malloc_holds(libc),
            rss_after_drop_mb=rss_mb(), peak_rss_mb=peak_rss_mb())
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--only", default="", help="comma list of setting names")
    ap.add_argument("--setting", action="append", default=[], metavar="NAME=TUNABLES",
                    help="one more setting, e.g. pad=top_pad=1000000000:mmap_max=0")
    ap.add_argument("--out", default=None)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ns = ap.parse_args()
    if ns.child:
        return child(ns.rounds)

    settings = dict(SETTINGS)
    settings.update(s.split("=", 1) for s in ns.setting)
    only = set(filter(None, ns.only.split(",")))
    lines, summary = [], {}
    for name, setting in settings.items():
        if only and name not in only:
            continue
        env = {k: v for k, v in os.environ.items()
               if k != "GLIBC_TUNABLES" and not k.startswith("MALLOC_")}
        if setting:
            env["GLIBC_TUNABLES"] = tunables(setting)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", "--rounds", str(ns.rounds)],
            env=env, stdout=subprocess.PIPE, text=True,
        )
        rows = [json.loads(l) for l in proc.stdout.splitlines() if l.startswith("{")]
        for row in rows:
            row["setting"] = name
            lines.append(row)
            print(json.dumps(row), flush=True)
        if proc.returncode != 0:
            print(json.dumps(dict(setting=name, failed=proc.returncode)), flush=True)
            continue
        later = [r for r in rows if r.get("round", 0) >= 1]
        first = next(r for r in rows if r.get("round") == 0)
        med = lambda key: statistics.median(r[key] for r in later)  # noqa: E731
        summary[name] = dict(
            first_round_faults=first["minor_faults"], minor_faults=med("minor_faults"),
            kick_s=med("kick_s"), wait_s=med("wait_s"),
            rss_after_drop_mb=med("rss_after_drop_mb"), peak_rss_mb=later[-1]["peak_rss_mb"],
            held=later[-1]["held"],
        )
    lines.append(dict(summary=summary))
    print(json.dumps(lines[-1]), flush=True)
    if ns.out:
        os.makedirs(os.path.dirname(os.path.abspath(ns.out)), exist_ok=True)
        with open(ns.out, "w") as f:
            for row in lines:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
