"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached: what the chip's compiler refuses fails here, with no chip.

Each test lowers a `kernels/ops.py` wrapper for one device of a described
`v5e:2x2` topology, with interpret mode steered off, compiles it, and
checks that the compiled program holds the kernel (`tpu_custom_call`).
The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one running this file
loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.power_step import fused_power_vmem_bytes
from repro.roofline import hw


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def native(monkeypatch):
    """Compile the kernels as on the chip, not in interpret mode."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _compile(fn, *args):
    # the library runs f32 solves at full matmul precision (linalg.api)
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn).lower(*args).compile()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


#: (name, args builder, kernel call) at the shapes the chip smoke runs: the
#: paper's 65536 x 4096 matrix at k = 118 (s = 128), its 4096-row streamed
#: panels, and a block-ELL pack of 512 row blocks x 2 tiles
CASES = {
    "sketch_matmul": (
        lambda d: (_sds(d, (65536, 4096)), _sds(d, (), jnp.uint32)),
        lambda a, seed: ops.sketch_matmul(a, 128, seed)),
    "sketch_gram": (
        lambda d: (_sds(d, (4096, 4096)), _sds(d, (), jnp.uint32)),
        lambda a, seed: ops.sketch_gram(a, 128, seed)),
    "sketch_power": (
        lambda d: (_sds(d, (65536, 4096)), _sds(d, (), jnp.uint32)),
        lambda a, seed: ops.sketch_power(a, 128, seed)),
    "power_step": (
        lambda d: (_sds(d, (65536, 4096)), _sds(d, (4096, 128))),
        lambda a, x: ops.power_step(a, x, with_gram=True)),
    "gram": (
        lambda d: (_sds(d, (65536, 128)),),
        ops.gram),
    "tri_solve_right": (
        lambda d: (_sds(d, (65536, 128)), _sds(d, (128, 128))),
        ops.tri_solve_right),
    "spmm_sketch": (
        lambda d: (_sds(d, (512, 2, 128, 128)), _sds(d, (512, 2), jnp.int32),
                   _sds(d, (), jnp.uint32)),
        lambda data, cols, seed: ops.spmm_sketch(data, cols, 128, seed,
                                                 m=65536)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, native):
    make_args, call = CASES[name]
    compiled = _compile(call, *make_args(one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def _widest_fused_n(s: int) -> int:
    """The widest 128-multiple n the fusion gate admits at sketch width s."""
    n = 128
    while fused_power_vmem_bytes(n + 128, s) <= hw.VMEM_BYTES:
        n += 128
    return n


@pytest.mark.parametrize("kernel", ["sketch_power", "power_step"])
def test_fusion_gate_boundary_compiles(kernel, one_chip, native):
    """The widest strip the planner would fuse at s = 128 compiles within
    the VMEM limit the kernels request: plan and compiler agree."""
    n = _widest_fused_n(128)
    assert fused_power_vmem_bytes(n, 128) <= hw.VMEM_BYTES
    a = _sds(one_chip, (256, n))
    if kernel == "sketch_power":
        compiled = _compile(lambda a, seed: ops.sketch_power(a, 128, seed),
                            a, _sds(one_chip, (), jnp.uint32))
    else:
        compiled = _compile(lambda a, x: ops.power_step(a, x, with_gram=True),
                            a, _sds(one_chip, (n, 128)))
    assert "tpu_custom_call" in compiled.as_text()
