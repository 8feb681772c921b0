"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: blocks off the (8, 128) tiling, VMEM past the scoped
limit, scalar prefetch past the 1 MiB SMEM. These tests compile each
kernel at the paper's widths (d=768, m=96, ksub=256, k=10; LSH at 4 x
128-bit signatures) for one chip of
a described v5e:2x2 topology — no chip needed — so a refused kernel fails
here instead of on the chip. The topology is described only inside the
module fixture: describing it loads the TPU library, which one process at
a time may hold.
"""
import contextlib
import importlib
import os
import signal
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ivf = importlib.import_module("repro.kernels.ivf_adc")
pq = importlib.import_module("repro.kernels.pq_adc")
tk = importlib.import_module("repro.kernels.topk_distance")
hm = importlib.import_module("repro.kernels.hamming")

D, M, KSUB, K = 768, 96, 256, 10
NPROBE, SPP, BLK = 16, 8, 32
B, Q, G, QBLK = 4096, 64, 64, 8
LIMIT_S = 120  # per compile; each takes a few seconds


@contextlib.contextmanager
def time_limit(seconds: int):
    def expire(*_):
        raise TimeoutError(f"compile took over {seconds}s")
    old = signal.signal(signal.SIGALRM, expire)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def compile_for(one_chip, fn, *shapes):
    """Lower + compile ``fn`` for the described chip; returns the
    executable's text (the kernels appear as tpu_custom_call)."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    t0 = time.perf_counter()
    with time_limit(LIMIT_S):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert time.perf_counter() - t0 < LIMIT_S
    assert "tpu_custom_call" in text
    return text


BLOCKS = ((B, BLK, M), jnp.uint8), ((B, BLK), jnp.int32)
LUTS = ((Q, M, KSUB), jnp.float32), ((Q, NPROBE), jnp.float32)


@pytest.mark.parametrize("lut_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_queries", [Q, 512])
def test_ivf_adc_per_query_compiles(one_chip, lut_dtype, n_queries):
    # 512 queries x 2048 steps overflow one call's SMEM visit table: the
    # wrapper loops over query chunks
    spp = SPP if n_queries == Q else 128
    compile_for(
        one_chip,
        lambda c, i, v, l, co: ivf.ivf_adc(c, i, v, l, co, k=K,
                                           steps_per_probe=spp,
                                           lut_dtype=lut_dtype),
        *BLOCKS, ((n_queries, NPROBE * spp), jnp.int32),
        ((n_queries, M, KSUB), jnp.float32),
        ((n_queries, NPROBE), jnp.float32))


@pytest.mark.parametrize("lut_dtype", ["float32", "bfloat16"])
def test_ivf_adc_blocked_compiles(one_chip, lut_dtype):
    compile_for(
        one_chip,
        lambda c, i, sb, sq, st, l, co: ivf.ivf_adc_blocked(
            c, i, sb, sq, st, l, co, k=K, steps_per_probe=SPP,
            lut_dtype=lut_dtype),
        *BLOCKS, ((G,), jnp.int32), ((G, QBLK), jnp.int32),
        ((G, QBLK), jnp.int32), *LUTS)


@pytest.mark.parametrize("lut_dtype", ["float32", "bfloat16"])
def test_ivf_adc_run_resident_compiles(one_chip, lut_dtype):
    compile_for(
        one_chip,
        lambda c, i, rb, rs, rl, sq, st, l, co: ivf.ivf_adc_run_resident(
            c, i, rb, rs, rl, sq, st, l, co, k=K, steps_per_probe=SPP,
            lut_dtype=lut_dtype),
        *BLOCKS, ((G,), jnp.int32), ((G,), jnp.int32), ((G,), jnp.int32),
        ((G, QBLK), jnp.int32), ((G, QBLK), jnp.int32), *LUTS)


@pytest.mark.parametrize("lut_dtype", ["float32", "bfloat16"])
def test_pq_adc_compiles(one_chip, lut_dtype):
    n = 1 << 16
    compile_for(
        one_chip,
        lambda c, l, b: pq.pq_adc(c, l, k=K, bias=b, lut_dtype=lut_dtype),
        ((n, M), jnp.int32), ((Q, M, KSUB), jnp.float32),
        ((n,), jnp.float32))


def test_topk_distance_compiles(one_chip):
    n = 1 << 16
    compile_for(one_chip,
                lambda c, q, b: tk.topk_distance(c, q, k=K, bias=b),
                ((n, D), jnp.float32), ((Q, D), jnp.float32),
                ((n,), jnp.float32))


def test_hamming_compiles(one_chip):
    # the LSH engine's ranking pass: 4 tables x 128-bit signatures
    n = 1 << 16
    compile_for(one_chip, lambda q, c: hm.hamming(q, c),
                ((4, Q, 4), jnp.uint32), ((4, n, 4), jnp.uint32))
