"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a
``v5e:2x2`` host and the TPU compiler lowers each kernel for one of its
chips, so a BlockSpec the Mosaic tiler refuses or a tile that overflows
VMEM fails here, where interpret-mode parity tests cannot see it.  Shapes
are Qwen1.5-MoE's published widths in bf16 (16 heads x 128, d_model
2048, 60 experts x d_ff 1408) at the shapes ``chip_smoke.py`` serves:
8 decode slots over a paged pool of 16-token blocks, and 256-token
prefill chunks over the slab scratch viewed as 128-token blocks; and the
decode kernel at the chip benchmark's shape, whose compiled custom call
the benchmark's ``paged_attention_roofline`` reader must find.

The topology is described only inside the module fixture: the TPU
library admits one process at a time, and pytest workers import every
test file.
"""
import importlib.util
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.grouped_ffn import grouped_ffn
from repro.kernels.paged_attention.ops import decode_plan, paged_attention
from repro.kernels.paged_attention.paged_attention import PagedLayoutError

QWEN = get_config("qwen15-moe-a27b")
H, HD = QWEN.num_heads, QWEN.resolved_head_dim
D, F = QWEN.d_model, QWEN.moe.d_ff_expert
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _paged_args(sharding, *, batch, q_len, hkv, hd, block_size, n_blocks,
                pool_blocks):
    return (_spec((batch, q_len, hkv, hd), BF16, sharding),
            _spec((1, pool_blocks * block_size, hkv, hd), BF16, sharding),
            _spec((1, pool_blocks * block_size, hkv, hd), BF16, sharding),
            _spec((batch, n_blocks), jnp.int32, sharding),
            _spec((batch,), jnp.int32, sharding))


@pytest.mark.parametrize("phase,batch,q_len,block_size,n_blocks,pool_blocks", [
    # decode: 8 slots x 48 blocks of 16 tokens (768-token chains) + null
    ("decode", 8, 1, 16, 48, 8 * 48 + 1),
    # prefill: a 256-token chunk over the [1, 768] slab, 6 blocks of 128
    ("prefill", 1, 256, 128, 6, 6),
])
def test_paged_attention_compiles(one_chip, phase, batch, q_len, block_size,
                                  n_blocks, pool_blocks):
    fn = jax.jit(lambda q, k, v, bt, cl: paged_attention(
        q, k, v, bt, cl, block_size=block_size, interpret=False))
    args = _paged_args(one_chip, batch=batch, q_len=q_len, hkv=H, hd=HD,
                       block_size=block_size, n_blocks=n_blocks,
                       pool_blocks=pool_blocks)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_rejects_unaligned_head_dim(one_chip):
    """A kv head's lanes of the [blocks, block_size, Hkv * hd] pool view
    are only tileable when hd fills whole 128-lane tiles."""
    fn = jax.jit(lambda q, k, v, bt, cl: paged_attention(
        q, k, v, bt, cl, block_size=16, interpret=False))
    args = _paged_args(one_chip, batch=8, q_len=1, hkv=4, hd=64,
                       block_size=16, n_blocks=8, pool_blocks=65)
    with pytest.raises(PagedLayoutError, match="head_dim % 128"):
        fn.lower(*args)


def _reader_pattern(metric):
    """The kernel pattern of one of the chip benchmark's roofline readers."""
    bench = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    spec = importlib.util.spec_from_file_location(
        f"reader_{metric}", bench / "metrics" / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.KERNEL


def _custom_calls(compiled):
    """The compiled module's Mosaic calls, printed as a chip trace names
    its ops: with each operand's shape."""
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    opts.print_metadata = False
    opts.print_backend_config = False
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


# the qwen15-L6.chat cell's decode call (32 slots, 288-block tables over a
# 2,800-block pool plus the null block), and one device's share of it on
# four chips (4 kv heads), where the byte target gives the largest P
@pytest.mark.parametrize("hkv", [H, H // 4])
def test_paged_decode_compiles_for_the_benchmark_reader(one_chip, hkv):
    fn = jax.jit(lambda q, k, v, bt, cl: paged_attention(
        q, k, v, bt, cl, block_size=16, interpret=False))
    args = _paged_args(one_chip, batch=32, q_len=1, hkv=hkv, hd=HD,
                       block_size=16, n_blocks=288, pool_blocks=2801)
    pages = decode_plan(args[1], block_size=16,
                        n_blocks=288)["pages_per_step"]
    assert pages == (H // hkv) * decode_plan(
        _paged_args(one_chip, batch=32, q_len=1, hkv=H, hd=HD,
                    block_size=16, n_blocks=288, pool_blocks=2801)[1],
        block_size=16, n_blocks=288)["pages_per_step"]
    calls = _custom_calls(fn.lower(*args).compile())
    assert len(calls) == 1
    assert any(re.search(p, calls[0])
               for p in _reader_pattern("paged_attention_roofline"))
    assert not any(re.search(p, calls[0])
                   for p in _reader_pattern("moe_gmm_roofline"))


@pytest.mark.parametrize("groups", [60, 15])   # one chip / one of four
def test_moe_gmm_compiles(one_chip, groups):
    m = groups * 128                              # one row tile per group
    fn = jax.jit(lambda x, wi, wg, wo, sizes: grouped_ffn(
        x, wi, wo, sizes, w_gate=wg, act="silu", use_pallas=True,
        interpret=False))
    compiled = fn.lower(
        _spec((m, D), BF16, one_chip),
        _spec((groups, D, F), BF16, one_chip),
        _spec((groups, D, F), BF16, one_chip),
        _spec((groups, F, D), BF16, one_chip),
        _spec((groups,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["paged_attention", "moe_gmm"])
def test_kernels_carry_their_names(one_chip, kernel):
    """Each Pallas call names its kernel, so a chip trace's custom call
    reads ``paged_attention`` / ``moe_gmm`` instead of ``_kernel``."""
    if kernel == "paged_attention":
        fn = jax.jit(lambda q, k, v, bt, cl: paged_attention(
            q, k, v, bt, cl, block_size=16, interpret=False))
        args = _paged_args(one_chip, batch=8, q_len=1, hkv=H, hd=HD,
                           block_size=16, n_blocks=48, pool_blocks=8 * 48 + 1)
    else:
        fn = jax.jit(lambda x, wi, wg, wo, sizes: grouped_ffn(
            x, wi, wo, sizes, w_gate=wg, act="silu", use_pallas=True,
            interpret=False))
        args = (_spec((15 * 128, D), BF16, one_chip),
                _spec((15, D, F), BF16, one_chip),
                _spec((15, D, F), BF16, one_chip),
                _spec((15, F, D), BF16, one_chip),
                _spec((15,), jnp.int32, one_chip))
    text = fn.lower(*args).as_text()
    assert f'kernel_name = "{kernel}"' in text
