"""Global device-mesh registry.

Reference parity: platform/collective_helper.h NCCLCommContext (ring registry)
+ fleet/base/topology.py CommunicateTopology. TPU-native: ONE logical N-D mesh
over all devices; "rings" are named axes. Axis names follow the reference's
hybrid order ["data", "pipe", "sharding", "model"] (topology.py:36) plus
"sep"/"expert" for sequence/expert parallel.
"""
from __future__ import annotations

import contextlib

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding

_STATE = {"mesh": None, "axis_degrees": None}

# The mesh a compiled program's inputs are laid out on, visible while that
# program is traced (to_static sets it): a tracer carries no placement, and
# code that must map a kernel over the mesh by hand (Mosaic kernels cannot
# be partitioned automatically) has nothing else to observe.
_TRACE_MESH = [None]


def mesh_of(values):
    """The multi-device Mesh the given concrete arrays are laid out on, or
    None when every one of them sits on a single device."""
    for v in values:
        sh = getattr(v, "sharding", None)
        if sh is None or isinstance(v, jax.core.Tracer) \
                or len(sh.device_set) == 1:
            continue
        if isinstance(sh, NamedSharding):
            return sh.mesh
        reg = _STATE["mesh"]
        if reg is not None and set(reg.devices.flat) == set(sh.device_set):
            return reg
        raise ValueError(
            f"array laid out over {len(sh.device_set)} devices by {sh!r}, "
            f"which names no mesh and matches no mesh built by build_mesh()")
    return None


@contextlib.contextmanager
def trace_mesh(mesh):
    prev, _TRACE_MESH[0] = _TRACE_MESH[0], mesh
    try:
        yield
    finally:
        _TRACE_MESH[0] = prev


def operand_mesh(value):
    """The mesh `value`'s computation is spread over: its own layout when it
    is concrete, the enclosing compiled program's when it is a tracer."""
    if isinstance(value, jax.core.Tracer):
        return _TRACE_MESH[0]
    return mesh_of((value,))

HYBRID_AXES = ("data", "pipe", "sharding", "sep", "model")


def build_mesh(axis_degrees=None, devices=None):
    """Create the global mesh. axis_degrees: dict axis->degree; product must
    equal len(devices). Default: all devices on the 'data' axis."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if axis_degrees is None:
        axis_degrees = {"data": n}
    names = [a for a in HYBRID_AXES if a in axis_degrees] + \
        [a for a in axis_degrees if a not in HYBRID_AXES]
    degrees = [axis_degrees[a] for a in names]
    total = int(np.prod(degrees))
    if total != n:
        # pad missing factor onto data axis
        if "data" in axis_degrees:
            raise ValueError(
                f"axis degrees {axis_degrees} do not cover {n} devices")
        names = ["data"] + names
        degrees = [n // total] + degrees
    arr = np.asarray(devices).reshape(degrees)
    mesh = Mesh(arr, tuple(names))
    _STATE["mesh"] = mesh
    _STATE["axis_degrees"] = dict(zip(names, degrees))
    return mesh


def set_mesh(mesh):
    _STATE["mesh"] = mesh
    _STATE["axis_degrees"] = dict(zip(mesh.axis_names, mesh.devices.shape))
    return mesh


def get_mesh():
    if _STATE["mesh"] is None:
        build_mesh()
    return _STATE["mesh"]


def global_mesh():
    return get_mesh()


def axis_degree(axis):
    m = get_mesh()
    if axis in m.axis_names:
        return m.devices.shape[m.axis_names.index(axis)]
    return 1


def shard_map(fn, mesh, in_specs, out_specs, check_rep=True):
    """The one shard_map call site of the lane engines (``jax.shard_map``
    spells replication checking ``check_vma``)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)
