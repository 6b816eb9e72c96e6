"""AOT compiles of the main path's region programs for a described TPU v5e
chip (no chip attached): the compiler refuses here what it would refuse on
the chip — unaligned block shapes, a fusion pass abort, too much VMEM — at
no chip time.  Each program must keep its Pallas kernel compiled
(``tpu_custom_call``), never the interpreter.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker imports
every test file.
"""
import jax
import numpy as np
import pytest

from repro.controller.kernels import get_kernel
from repro.core.preemption import PreemptFlag
from repro.core.reconfig import ReconfigEngine
from repro.kernels import pallas_support
from repro.kernels.blur.tasks import make_image


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip, with Pallas resolving to its compiled
    (Mosaic) form as it does on a TPU, and JAX's persistent cache off: a
    compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    backends = pallas_support._COMPILED_BACKENDS
    cache_on = jax.config.jax_enable_compilation_cache
    pallas_support._COMPILED_BACKENDS = backends + (jax.default_backend(),)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        yield topo.devices[0]
    finally:
        pallas_support._COMPILED_BACKENDS = backends
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        jax.clear_caches()


def _compile(kernel, bundle, device):
    """The region's bitstream generation, aimed at the described chip."""
    return ReconfigEngine()._compile(get_kernel(kernel), bundle, device,
                                     program="chunk")


def _assert_kernel_compiled(compiled):
    text = compiled.as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["MedianBlur", "GaussianBlur"])
def test_blur_chunk_compiles_for_v5e(chip, kernel):
    """The paper's blur task at its largest size (600 px, padded to 640)."""
    img = make_image(np.random.default_rng(0), 600)
    assert img.shape == (642, 642)
    bundle = get_kernel(kernel).bundle(img, np.zeros_like(img), H=600,
                                       W=600, iters=3)
    _assert_kernel_compiled(_compile(kernel, bundle, chip))


def _zeros(shape, dtype=np.float32):
    # a compile reads shapes only: a zero-stride view allocates nothing
    return np.broadcast_to(np.zeros((), dtype), shape)


def test_attention_prefill_chunk_compiles_for_v5e(chip):
    """The serving prefill at Mistral 7B's attention widths: a 2048-wide
    context block per (row, head) and 16-position segments."""
    from repro.serving.attention import (META_W, MISTRAL_7B, PREFILL_OUT_W,
                                         _row_offsets,
                                         register_attention_kernels)

    p = MISTRAL_7B
    pre, _ = register_attention_kernels(p)
    PB, P = 1, p.max_ctx
    kv = _zeros((PB, P, p.kv_heads, p.head_dim))
    bundle = get_kernel(pre).bundle(
        _zeros((PB, PREFILL_OUT_W), np.int32), kv, kv,
        _zeros((PB, P), np.int32), _zeros((PB, META_W), np.int32),
        _zeros((_row_offsets(p)[-1], p.d_model)), PB=PB, P=P, vocab=p.vocab)
    _assert_kernel_compiled(_compile(pre, bundle, chip))


def test_paged_decode_chunk_compiles_for_v5e(chip):
    """The serving decode round at Mistral 7B's attention widths: 4 slots
    of 128 16-position pages; per-row positions ride scalar prefetch."""
    from repro.serving.attention import (MISTRAL_7B, _row_offsets,
                                         register_attention_kernels)

    p = MISTRAL_7B
    _, dec = register_attention_kernels(p)
    S, R = 4, 4
    pool = _zeros((S * p.blocks_per_seq + 1, p.block_size, p.kv_heads,
                   p.head_dim))
    bundle = get_kernel(dec).bundle(
        _zeros((S, R), np.int32), pool, pool,
        _zeros((S, p.table_width), np.int32),
        _zeros((_row_offsets(p)[-1], p.d_model)), S=S, R=R, vocab=p.vocab)
    _assert_kernel_compiled(_compile(dec, bundle, chip))


def test_megakernel_flag_refused_on_tpu_device(topo):
    """The megakernel's host-written flag is refused from the platform,
    before any buffer pointer is read."""
    with pytest.raises(RuntimeError, match="host-mappable.*tpu"):
        PreemptFlag(topo.devices[0])


def test_bitstreams_are_keyed_per_device(topo):
    eng = ReconfigEngine()
    sig = (((8, 8), "float32"),)
    keys = {eng.cache_key("MedianBlur", sig, (1,), devices=[d])
            for d in topo.devices}
    assert len(keys) == len(topo.devices) == 4
