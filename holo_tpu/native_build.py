"""Lazy build + ctypes loader for the C++ native components.

The native pieces (scalar SPF baseline, runtime core) are compiled on
first use into ``native/build/`` with g++ — no pip/cmake dependency —
and loaded via ctypes.  The library's file name carries a hash of the
source text, the compiler flags and the host CPU, so an edited source
rebuilds and a ``native/build/`` copied from another machine (the
objects are ``-march=native``) is never loaded.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
NATIVE = REPO / "native"
BUILD = NATIVE / "build"

_FLAGS = ["-O3", "-march=native", "-std=c++17", "-shared", "-fPIC"]


def _host_id() -> str:
    """What ``-march=native`` resolves against: the CPU model and its
    feature flags (first core), plus the machine name."""
    ident = [platform.node(), platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("model name", "flags")):
                    ident.append(line.strip())
                if line.startswith("flags"):
                    break
    except OSError:
        pass
    return "\n".join(ident)


def _ensure(stem: str, sources: list[str]) -> Path:
    srcs = [NATIVE / s for s in sources]
    h = hashlib.sha256()
    for s in srcs:
        h.update(s.read_bytes())
    h.update(" ".join(_FLAGS).encode())
    h.update(_host_id().encode())
    so = BUILD / f"{stem}-{h.hexdigest()[:16]}.so"
    if so.exists():
        return so
    BUILD.mkdir(parents=True, exist_ok=True)
    # Build beside the target and rename: two processes racing on a
    # cold tree must never load a half-written object.
    tmp = so.with_suffix(f".{os.getpid()}.tmp")
    cmd = ["g++", *_FLAGS, *[str(s) for s in srcs], "-o", str(tmp)]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"native build failed ({' '.join(cmd)}):\n{proc.stderr}"
        )
    os.replace(tmp, so)
    return so


_spf_lib = None


def spf_baseline_lib() -> ctypes.CDLL:
    global _spf_lib
    if _spf_lib is None:
        lib = ctypes.CDLL(str(_ensure("libspf_baseline", ["spf_baseline.cpp"])))
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C")
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C")
        u64p = np.ctypeslib.ndpointer(np.uint64, flags="C")
        lib.holo_spf_scalar.argtypes = [
            ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p, i32p,
            ctypes.c_void_p, ctypes.c_int32, i32p, i32p, i32p, u64p, u8p,
        ]
        lib.holo_spf_scalar.restype = None
        lib.holo_spf_scalar_batch.argtypes = [
            ctypes.c_int32, ctypes.c_int32, i32p, i32p, i32p, i32p,
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32, i32p, u8p,
        ]
        lib.holo_spf_scalar_batch.restype = None
        _spf_lib = lib
    return _spf_lib


def native_spf(topo, edge_mask=None):
    """C++ scalar SPF: returns (dist, parent, hops, nh_u64) numpy arrays."""
    if topo.n_atoms() > 64:
        raise ValueError(
            f"native baseline supports <= 64 next-hop atoms, got {topo.n_atoms()}"
        )
    lib = spf_baseline_lib()
    n, e = topo.n_vertices, topo.n_edges
    dist = np.empty(n, np.int32)
    parent = np.empty(n, np.int32)
    hops = np.empty(n, np.int32)
    nh = np.empty(n, np.uint64)
    is_router = np.ascontiguousarray(topo.is_router, np.uint8)
    mask_p = None
    if edge_mask is not None:
        mask_arr = np.ascontiguousarray(edge_mask, np.uint8)
        mask_p = mask_arr.ctypes.data_as(ctypes.c_void_p)
    lib.holo_spf_scalar(
        n, e,
        np.ascontiguousarray(topo.edge_src),
        np.ascontiguousarray(topo.edge_dst),
        np.ascontiguousarray(topo.edge_cost),
        np.ascontiguousarray(topo.edge_direct_atom),
        mask_p, topo.root, dist, parent, hops, nh, is_router,
    )
    return dist, parent, hops, nh


_runtime_lib = None


def runtime_core_lib() -> ctypes.CDLL:
    """C++ runtime core: timer wheel, MPSC rings, epoll poller."""
    global _runtime_lib
    if _runtime_lib is None:
        lib = ctypes.CDLL(str(_ensure("libruntime_core", ["runtime_core.cpp"])))
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C")
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C")
        u32p = np.ctypeslib.ndpointer(np.uint32, flags="C")
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C")
        lib.holo_wheel_new.restype = ctypes.c_void_p
        lib.holo_wheel_free.argtypes = [ctypes.c_void_p]
        lib.holo_wheel_create.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.holo_wheel_create.restype = ctypes.c_int32
        lib.holo_wheel_arm.argtypes = [ctypes.c_void_p, ctypes.c_int32, ctypes.c_double]
        lib.holo_wheel_cancel.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.holo_wheel_destroy.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.holo_wheel_advance.argtypes = [
            ctypes.c_void_p, ctypes.c_double, i64p, ctypes.c_int,
        ]
        lib.holo_wheel_advance.restype = ctypes.c_int
        lib.holo_ring_new.argtypes = [ctypes.c_uint32, ctypes.c_uint32]
        lib.holo_ring_new.restype = ctypes.c_void_p
        lib.holo_ring_free.argtypes = [ctypes.c_void_p]
        lib.holo_ring_push.argtypes = [ctypes.c_void_p, u8p, ctypes.c_uint32]
        lib.holo_ring_push.restype = ctypes.c_int
        lib.holo_ring_pop.argtypes = [ctypes.c_void_p, u8p, ctypes.c_uint32]
        lib.holo_ring_pop.restype = ctypes.c_int
        lib.holo_poller_new.restype = ctypes.c_int
        lib.holo_poller_add.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_uint32]
        lib.holo_poller_del.argtypes = [ctypes.c_int, ctypes.c_int]
        lib.holo_poller_wait.argtypes = [
            ctypes.c_int, ctypes.c_int, i32p, u32p, ctypes.c_int,
        ]
        lib.holo_poller_wait.restype = ctypes.c_int
        lib.holo_monotonic_now.restype = ctypes.c_double
        _runtime_lib = lib
    return _runtime_lib


def native_spf_batch_dist(topo, edge_masks) -> np.ndarray:
    """C++ serial what-if batch (distances only): the CPU baseline workload."""
    lib = spf_baseline_lib()
    n, e = topo.n_vertices, topo.n_edges
    b = edge_masks.shape[0]
    out = np.empty((b, n), np.int32)
    masks = np.ascontiguousarray(edge_masks, np.uint8)
    lib.holo_spf_scalar_batch(
        n, e,
        np.ascontiguousarray(topo.edge_src),
        np.ascontiguousarray(topo.edge_dst),
        np.ascontiguousarray(topo.edge_cost),
        np.ascontiguousarray(topo.edge_direct_atom),
        masks.ctypes.data_as(ctypes.c_void_p), b, topo.root, out,
        np.ascontiguousarray(topo.is_router, np.uint8),
    )
    return out
