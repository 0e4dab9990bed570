"""Compile-only checks against the TPU's own compiler, without a chip.

The CPU suite runs every pallas kernel in interpret mode, which accepts
block shapes, VMEM footprints and partitionings the chip's compiler
refuses. Here each main-path kernel is lowered at the trf / cnn REAL widths
for a DESCRIBED v5e:2x2 (``jax.experimental.topologies`` — libtpu's
compiler is installed, no device is attached) and must come out as a
``tpu_custom_call``. Nothing runs: a compile that passes is not a chip run
(``chip_smoke.py`` is). The conftest keeps the persistent compile cache off
in this process — such an entry could be written but never read back
without a chip.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
# no chip is attached, so another process that has libtpu loaded (an xdist
# worker, a second run) is no reason to refuse this one
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from spacy_ray_tpu.ops import flash_attention as fa
from spacy_ray_tpu.ops import fused_update as fu
from spacy_ray_tpu.ops import int8_matmul as i8
from spacy_ray_tpu.ops import pallas_kernels as pk
from spacy_ray_tpu.parallel import context as pctx
from spacy_ray_tpu.parallel import ring_attention as ra
from spacy_ray_tpu.parallel.mesh import build_mesh


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


def _compiles_to_kernel(fn, *args) -> None:
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _flash_loss(q, k, v, mask):
    return jnp.sum(fa.flash_attention(q, k, v, mask).astype(jnp.float32) ** 2)


_HYPER = fu.FusedHyper(
    kind="adam", b1=0.9, b2=0.999, eps=1e-8, grad_clip=1.0, l2_grad=0.0,
    l2_decay=0.01,
)

# (id, function, [(shape, dtype), ...]) — the shapes the trf trunk
# (32x256 and 4x512 docs, 12 heads of 64) and the cnn pipelines (hash
# tables of 2000 / 5000 rows x 96) really run
SINGLE_CHIP_CASES = [
    ("hash_embed_fwd_2000x96", pk._pallas_lookup,
     [((2000, 96), jnp.float32), ((16384, 4), jnp.int32)]),
    ("hash_embed_grad_5000x96",
     jax.grad(lambda t, ids: jnp.sum(jnp.sin(pk._pallas_lookup(t, ids)))),
     [((5000, 96), jnp.float32), ((16384, 4), jnp.int32)]),
    ("flash_fwd_32x256x12x64", fa.flash_attention,
     [((32, 256, 12, 64), jnp.bfloat16)] * 3 + [((32, 256), jnp.bool_)]),
    ("flash_grad_32x256x12x64", jax.grad(_flash_loss, (0, 1, 2)),
     [((32, 256, 12, 64), jnp.bfloat16)] * 3 + [((32, 256), jnp.bool_)]),
    ("flash_fwd_4x512x12x64", fa.flash_attention,
     [((4, 512, 12, 64), jnp.bfloat16)] * 3 + [((4, 512), jnp.bool_)]),
    ("flash_grad_4x512x12x64", jax.grad(_flash_loss, (0, 1, 2)),
     [((4, 512, 12, 64), jnp.bfloat16)] * 3 + [((4, 512), jnp.bool_)]),
] + [
    # the fused update takes each leaf where it lies: trf's widths, the
    # routed trunk's (three dimensions merged; a table whose rows are no
    # multiple of a block; 576 = 4.5 x 128 lanes), sm's width 96, the
    # pattern trunk's; a
    # shadowed leaf takes its gradient as bf16 and writes its shadow
    (f"fused_update_{'x'.join(map(str, shape))}_{jnp.dtype(g_dtype).name}"
     + ("_shadow" if shadow else ""),
     lambda p, g, m, v, s, shadow=shadow, block=block: fu._kernel_leaf(
         p, g, m, v, s, _HYPER, shadow_dtype=shadow, interpret=False,
         block_bytes=block),
     [(shape, jnp.float32), (shape, g_dtype)] + [(shape, jnp.float32)] * 2
     + [((6,), jnp.float32)])
    for shape, g_dtype, shadow, block in (
        ((768, 3072), jnp.float32, jnp.bfloat16, fu.BLOCK_BYTES),
        ((20000, 768), jnp.float32, None, fu.BLOCK_BYTES),
        ((16, 768, 2048), jnp.bfloat16, jnp.bfloat16, fu.BLOCK_BYTES),
        ((16032, 2048), jnp.float32, None, fu.BLOCK_BYTES),
        ((2048, 576), jnp.bfloat16, jnp.bfloat16, fu.BLOCK_BYTES),
        ((512, 8192), jnp.bfloat16, jnp.bfloat16, fu.BLOCK_BYTES),
        ((2000, 96), jnp.float32, None, fu.BLOCK_BYTES),
        # the pattern trunk's (models/hybrid_ssm.py): the convolution's four
        # taps (rows under a tile), the input projection and the step's 64
        # columns of it (half a lane tile), experts of 1856 = 14.5 x 128
        ((4, 6144), jnp.float32, None, fu.BLOCK_BYTES),
        ((2688, 10240), jnp.bfloat16, jnp.bfloat16, fu.BLOCK_BYTES),
        ((2688, 64), jnp.float32, None, fu.BLOCK_BYTES),
        ((8, 2688, 1856), jnp.bfloat16, jnp.bfloat16, fu.BLOCK_BYTES),
        ((8, 1856, 2688), jnp.bfloat16, jnp.bfloat16, fu.BLOCK_BYTES),
        # the probe's own leaf, both forms: what a chip compiles first
        ((3, 16, 160), jnp.float32, None, 32 * 1024),
        ((3, 16, 160), jnp.bfloat16, jnp.bfloat16, 32 * 1024),
    )
] + [
    # bf16 activations are what a trunk hands over under compute_dtype
    # "auto" on a TPU; f32 ones take the kernel's HIGHEST-precision dot
    (f"int8_matmul_512x{K}x{N}_{jnp.dtype(dtype).name}",
     lambda x, q, s: i8._int8_matmul_raw(x, q, s, interpret=False),
     [((512, K), dtype), ((K, N), jnp.int8), ((N,), jnp.float32)])
    for K, N in ((768, 3072), (3072, 768))
    for dtype in (jnp.bfloat16, jnp.float32)
]


@pytest.mark.parametrize(
    "fn,operands", [c[1:] for c in SINGLE_CHIP_CASES],
    ids=[c[0] for c in SINGLE_CHIP_CASES],
)
def test_kernel_compiles_for_v5e(topo, fn, operands):
    one_chip = SingleDeviceSharding(topo.devices[0])
    _compiles_to_kernel(fn, *[
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in operands
    ])


@pytest.fixture
def flash_armed(monkeypatch):
    # the gate asks jax.default_backend(), which is the CPU here: steer it
    # in the test, the program has no option for this
    monkeypatch.setattr(fa.GATE, "armed", True)


@pytest.mark.parametrize("mode", ["fwd", "grad"])
def test_sharded_flash_compiles_on_a_four_chip_data_mesh(topo, flash_armed, mode):
    """``train --n-workers 4``: attention() puts the kernel in a shard_map
    that is manual over EVERY mesh axis — the TPU lowering refuses a kernel
    while any axis of the mesh is still automatic."""
    mesh = build_mesh(n_data=4, devices=topo.devices)
    qkv = jax.ShapeDtypeStruct(
        (32, 256, 12, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", None, None, None)),
    )
    mask = jax.ShapeDtypeStruct(
        (32, 256), jnp.bool_, sharding=NamedSharding(mesh, P("data", None))
    )

    def loss(q, k, v, m):
        return jnp.sum(fa.attention(q, k, v, m).astype(jnp.float32) ** 2)

    fn = fa.attention if mode == "fwd" else jax.grad(loss, (0, 1, 2))
    with pctx.use_mesh(mesh):
        _compiles_to_kernel(fn, qkv, qkv, qkv, mask)


def test_ring_flash_compiles_on_a_context_mesh(topo, flash_armed):
    """Ring attention's flash blocks consume the kernel's logsumexp output
    and its cotangent: forward and backward, data x context = 2 x 2."""
    mesh = build_mesh(n_data=2, n_context=2, devices=topo.devices)
    qkv = jax.ShapeDtypeStruct(
        (4, 2048, 12, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", "context", None, None)),
    )
    mask = jax.ShapeDtypeStruct(
        (4, 2048), jnp.bool_, sharding=NamedSharding(mesh, P("data", "context"))
    )

    def loss(q, k, v, m):
        return jnp.sum(ra.ring_attention(q, k, v, m).astype(jnp.float32) ** 2)

    with pctx.use_mesh(mesh):
        compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            qkv, qkv, qkv, mask
        ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "collective-permute" in text


@pytest.mark.parametrize(
    "T,fits", [(512, True), (3968, True), (4608, False)],
)
def test_attention_vmem_gate_agrees_with_the_compiler(topo, T, fits):
    """attention_vmem_ok budgets the backward kernel against the compiler's
    scoped-VMEM limit: what the gate admits must compile, and the first
    length the compiler refuses (4608: 16.84M of 16.00M) must be gated."""
    assert fa.attention_vmem_ok(T, 128, 2) is fits
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [
        jax.ShapeDtypeStruct((4, T, 12, 64), jnp.bfloat16, sharding=one_chip)
    ] * 3 + [jax.ShapeDtypeStruct((4, T), jnp.bool_, sharding=one_chip)]
    lowered = jax.jit(jax.grad(_flash_loss, (0, 1, 2))).lower(*args)
    if fits:
        assert "tpu_custom_call" in lowered.compile().as_text()
    else:
        with pytest.raises(Exception, match="vmem"):
            lowered.compile()
