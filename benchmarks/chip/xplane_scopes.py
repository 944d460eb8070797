"""The scope and source location XLA recorded for each operation of a
profiler trace.

``trace.py`` reads the trace through ``jax.profiler.ProfileData``, which
gives each ``XLA Ops`` event its instruction text but not the metadata
the profiler keeps beside it: the op's scope path (stat ``tf_op``, e.g.
``jit(_step_tables_fn)/while/body/closed_call/moe/dot_general``) and
its source line (stat ``source``).  This module reads that metadata from
the ``.xplane.pb`` file with the XPlane protocol buffer schema that
ships with the installed TensorFlow, loaded from its file alone
(TensorFlow itself is not imported)."""
from __future__ import annotations

import importlib.util
import os
from typing import List, Optional, Tuple

from benchmarks.chip import trace


def _schema():
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    if not os.path.isfile(path):
        return None
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def op_metadata(path: str, device: int
                ) -> Optional[List[Tuple[str, str, str]]]:
    """(instruction text, scope path, source location) of every event
    kind of ``/device:TPU:<device>`` in the trace at ``path`` (a file,
    or the newest one under a directory; two programs may hold the same
    text, so a text can appear more than once); None when the schema or
    the plane is missing."""
    pb = _schema()
    if pb is None:
        return None
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    space = pb.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if plane.name != f"{trace.DEVICE_PREFIX}{device}":
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        out = []
        for em in plane.event_metadata.values():
            stats = {stat_names.get(s.metadata_id): s.str_value
                     for s in em.stats}
            out.append((em.name, stats.get("tf_op", ""),
                        stats.get("source", "")))
        return out
    return None
