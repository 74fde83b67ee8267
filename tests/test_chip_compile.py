"""The Pallas kernels compile for a TPU v5e at the published widths of
the configs that use them (qwen3-1.7b for attention; mistral-nemo-12b too
for paged decode). The chip is described, not attached
(jax.experimental.topologies): the TPU compiler refuses here what it would
refuse on the chip — a block shape off the (8, 128) tiling, an unsupported
primitive — which the interpret-mode tests cannot see. Nothing runs, so
this says nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and each test worker imports
every test file."""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# qwen3-1.7b attention: GQA 16/8, head_dim 128, bf16
NH, NKV, HD = 16, 8, 128
DT = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile can be written to the persistent cache but
    never read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _paged_decode(spec, B=32, nh=NH, nb=96):
    # served shapes: 16-token pages, a pool of twice the slots' tables plus
    # the null page; qwen3-1.7b by default (32 slots, 1536-token tables)
    from repro.kernels.paged_attention.kernel import paged_attention_kernel
    bs = 16
    P = 2 * B * nb + 1
    args = (spec((B, nh, HD), DT), spec((P, NKV, bs, HD), DT),
            spec((P, NKV, bs, HD), DT), spec((B, nb), jnp.int32),
            spec((B,), jnp.int32))
    return functools.partial(paged_attention_kernel, interpret=False), args


def _paged_decode_mistral(spec):
    # mistral-nemo-12b: GQA 32/8 (rep 4), 16 slots, 2064-token tables
    # (129 pages, prime: the last block of pages is partial)
    return _paged_decode(spec, B=16, nh=32, nb=129)


def _flash_attention(spec):
    from repro.kernels.flash_attention.ops import flash_attention
    S = 1024
    args = (spec((1, S, NH, HD), DT), spec((1, S, NKV, HD), DT),
            spec((1, S, NKV, HD), DT))
    return functools.partial(flash_attention, interpret=False), args


def _rglru_scan(spec):
    # recurrentgemma-9b recurrence: lru_width 4096, 1024 steps
    from repro.kernels.rglru_scan.ops import rglru_scan
    args = (spec((1, 1024, 4096), jnp.float32),
            spec((1, 1024, 4096), jnp.float32))
    return functools.partial(rglru_scan, interpret=False), args


def _ssd_scan(spec):
    # mamba2-130m mixer: 24 heads of 64, d_state 128, chunk 256, 1024 steps
    from repro.kernels.ssd_scan.ops import ssd_scan
    b, s, h, p, g, n = 1, 1024, 24, 64, 1, 128
    args = (spec((b, s, h, p), DT), spec((b, s, h), jnp.float32),
            spec((h,), jnp.float32), spec((b, s, g, n), DT),
            spec((b, s, g, n), DT))
    return functools.partial(ssd_scan, chunk_size=256, interpret=False), args


KERNELS = {"paged_decode": _paged_decode,
           "paged_decode_mistral": _paged_decode_mistral,
           "flash_attention": _flash_attention,
           "rglru_scan": _rglru_scan, "ssd_scan": _ssd_scan}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = KERNELS[name](spec)
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()
