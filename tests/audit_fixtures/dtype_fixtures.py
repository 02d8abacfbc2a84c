"""dtype-discipline fixtures: an f64 leak and an int8-path upcast
(positives); the disciplined int8 wire (negative)."""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from quiver_tpu.parallel.mesh import FEATURE_AXIS, make_mesh, shard_map
from quiver_tpu.tools.audit.audit_targets import Target


def _f64_leak():
    def run(x):
        # constant-free f64 region (convert/add only): lowers consistently
        # even when the audit process itself runs x64-disabled
        wide = jnp.asarray(x, jnp.float64)
        return (wide + wide).astype(jnp.float32)

    # trace under x64 so the f64 actually lands in the jaxpr — the leak
    # an accidentally-enabled flag (or a numpy f64 operand) produces
    with jax.enable_x64(True):
        return jax.jit(run).trace(jax.ShapeDtypeStruct((8,), jnp.float32))


def _a2a(dtype):
    mesh = make_mesh(2, data=1, feature=2)

    def body(codes):
        # codes is the (4,) local block of the int8 id/row stream
        routed = jax.lax.all_to_all(
            codes.astype(dtype).reshape(2, 2), FEATURE_AXIS, 0, 0
        )
        return routed.reshape(4).astype(jnp.float32)

    fn = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(FEATURE_AXIS),), out_specs=P(FEATURE_AXIS),
        check_vma=False,
    ))
    return fn.trace(jax.ShapeDtypeStruct((8,), jnp.int8))


def targets():
    src = ("tests/audit_fixtures/dtype_fixtures.py",)
    return [
        (Target("dtype_f64_leak", "x64 value inside the program",
                _f64_leak, src), True),
        # int8 tier path whose codes were dequantized BEFORE routing —
        # the wire carries f32, 4x the bytes
        (Target("dtype_int8_upcast", "f32 all_to_all on the int8 path",
                lambda: _a2a(jnp.float32), src,
                meta={"int8_path": True}), True),
        (Target("dtype_int8_wire", "int8 codes ride the all_to_all",
                lambda: _a2a(jnp.int8), src,
                meta={"int8_path": True}), False),
    ]
