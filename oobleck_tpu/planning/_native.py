"""ctypes bindings for the C++ planner (csrc/planner.cpp).

The reference binds its C++ planner with pybind11 (csrc/planning/bind.cpp);
pybind11 is not in this image, so the native side exposes a C API and this
module marshals flat arrays in and JSON out. The .so is built on demand with
the csrc Makefile and cached next to the source under a name that carries
the source's digest: the binary is git-ignored, so a checkout, a copy of
one, or an edit of planner.cpp each find (or build) the binary of THEIR
source, and file times — which a copy does not keep — decide nothing.
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import os
import subprocess
from pathlib import Path

import numpy as np

from oobleck_tpu.planning.templates import LayerProfile, PipelineTemplate

_CSRC = Path(__file__).resolve().parent.parent / "csrc"
_lib = None


def _load() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    digest = hashlib.sha256((_CSRC / "planner.cpp").read_bytes()).hexdigest()
    so = _CSRC / f"libplanner-{digest[:12]}.so"
    if not so.exists():
        # Built under a private name and renamed into place: a concurrent
        # process (test workers on a fresh checkout) never loads half a file.
        tmp = so.with_name(f"{so.stem}.{os.getpid()}.tmp.so")
        subprocess.run(
            ["make", "-C", str(_CSRC), f"TARGET={tmp.name}"],
            check=True, capture_output=True, text=True,
        )
        os.replace(tmp, so)
    lib = ctypes.CDLL(str(so))
    lib.planner_create_templates.restype = ctypes.c_char_p
    lib.planner_create_templates.argtypes = [
        ctypes.c_int,                      # num_layers
        ctypes.POINTER(ctypes.c_double),   # fwd
        ctypes.POINTER(ctypes.c_double),   # bwd
        ctypes.c_int,                      # num_ar
        ctypes.POINTER(ctypes.c_int),      # ar_chips
        ctypes.POINTER(ctypes.c_double),   # ar_in_host
        ctypes.POINTER(ctypes.c_int64),    # mem_params
        ctypes.POINTER(ctypes.c_int64),    # mem_activation
        ctypes.c_int, ctypes.c_int,        # min/max hosts
        ctypes.c_int,                      # chips_per_host
        ctypes.c_int,                      # num_threads
    ]
    lib.planner_free.restype = None
    _lib = lib
    return lib


def create_pipeline_templates(
    profiles: list[LayerProfile],
    num_hosts: tuple[int, int],
    chips_per_host: int,
    num_threads: int = 0,
) -> list[PipelineTemplate]:
    lib = _load()
    L = len(profiles)
    fwd = np.array([p.forward for p in profiles], dtype=np.float64)
    bwd = np.array([p.backward for p in profiles], dtype=np.float64)
    ar_chips_set = sorted({c for p in profiles for c in p.allreduce_in_host})
    ar_chips = np.array(ar_chips_set, dtype=np.int32)
    ar = np.array(
        [[p.allreduce_in_host.get(c, 0.0) for c in ar_chips_set] for p in profiles],
        dtype=np.float64,
    ).reshape(L, -1)
    mem_p = np.array([p.mem_params for p in profiles], dtype=np.int64)
    mem_a = np.array([p.mem_activation for p in profiles], dtype=np.int64)

    raw = lib.planner_create_templates(
        L,
        fwd.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        bwd.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        len(ar_chips_set),
        ar_chips.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        ar.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        mem_p.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        mem_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        num_hosts[0], num_hosts[1], chips_per_host, num_threads,
    )
    data = json.loads(raw.decode())
    lib.planner_free()
    return [PipelineTemplate.from_json(d, L) for d in data]
