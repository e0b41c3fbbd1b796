"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Compiled for a described ``v5e:2x2`` topology with the TPU's own compiler —
no chip is attached, so nothing runs: each test proves that Mosaic accepts
the kernel at xlstm-350m's largest leaf (the 50304 x 1024 embedding,
51,511,296 elements) and at one layer's ``w_q`` slice (1024 x 2048), and
that the compiled program holds the kernel (a ``tpu_custom_call``). The
top-k kernels, whose tile height follows the block size, also compile at
HuBERT-XLarge's widest leaf, so their tile is checked against VMEM.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.compressors import block_layout
from repro.kernels.fedams_ingest import fedams_ingest
from repro.kernels.fedams_update import fedams_update
from repro.kernels.sign_ef import sign_ef
from repro.kernels.topk_ef import topk_ef, topk_ef_sparse

SIZES = {"embed": 50304 * 1024, "layer_w_q": 1024 * 2048}
#: plus the 21-layer stack of HuBERT-XLarge's 1280 x 5120 FFN weight
TOPK_SIZES = {**SIZES, "hubert_ffn": 1280 * 5120 * 21}
HP = dict(eta=0.5, beta1=0.9, beta2=0.99, eps=1e-3)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _arg(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiles_with_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _layout(n):
    bs, nb = block_layout(n, 2048)
    return bs, nb, nb * bs


@pytest.mark.parametrize("size", TOPK_SIZES)
@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_topk_ef_compiles(one_chip, size, sparse):
    bs, nb, n = _layout(TOPK_SIZES[size])
    kernel = topk_ef_sparse if sparse else topk_ef
    x = _arg(one_chip, (n,))
    _compiles_with_kernel(
        lambda a, b: kernel(a, b, k=bs // 64, block=bs, interpret=False),
        x, x)


@pytest.mark.parametrize("size", SIZES)
def test_sign_ef_compiles(one_chip, size):
    bs, nb, n = _layout(SIZES[size])
    x = _arg(one_chip, (n,))
    _compiles_with_kernel(
        lambda a, b: sign_ef(a, b, block=bs, interpret=False), x, x)


@pytest.mark.parametrize("size", SIZES)
def test_fedams_update_compiles(one_chip, size):
    x = _arg(one_chip, (SIZES[size],))
    _compiles_with_kernel(
        lambda *a: fedams_update(*a, interpret=False, **HP), *[x] * 5)


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("clients,k", [(4, 32), (10, 1)])
def test_fedams_ingest_compiles(one_chip, size, dtype, clients, k):
    bs, nb, n = _layout(SIZES[size])
    sdt = jnp.dtype(dtype)
    args = [_arg(one_chip, (n,)), _arg(one_chip, (n,)),
            _arg(one_chip, (n,), sdt), _arg(one_chip, (n,), sdt),
            _arg(one_chip, (clients, nb, k)),
            _arg(one_chip, (clients, nb, k), jnp.int32)]
    if dtype == "int8":
        args += [_arg(one_chip, (nb,)), _arg(one_chip, (nb,))]
    _compiles_with_kernel(
        lambda *a: fedams_ingest(*a, n_div=clients, block=bs,
                                 state_dtype=dtype, interpret=False, **HP),
        *args)
