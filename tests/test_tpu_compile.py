"""Ahead-of-time compiles for one TPU v5e chip, made without the chip.

The TPU compiler is installed with JAX and compiles for a described
topology, so these tests catch what interpret mode cannot: a block layout
Mosaic refuses, an op v5e cannot lower, more VMEM than a kernel may use.
Nothing runs, so they say nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and a
worker that decides at import whether these tests exist would collect a
different test list from its siblings.  Where no topology can be described
the fixture skips.  The persistent compilation cache is off around the
compiles (an entry compiled for a described chip cannot be read back here).
"""
from __future__ import annotations

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fedavg.fedavg import TILE, fedavg_reduce
from repro.kernels.q8_block.q8_block import BLOCK, dequantize_q8, quantize_q8
from repro.kernels.quantize_f16.quantize_f16 import dequantize_f16, quantize_f16

SIZES = (44_426, 1_000_000)          # LeNet-5, and a 1M-parameter update
V5E_SCOPED_VMEM = 16 * 2 ** 20       # Mosaic's default scoped VMEM on v5e


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def _kernel_case(name: str, n: int, sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    rows = -(-n // BLOCK)
    if name == "quantize_q8":
        return partial(quantize_q8, interpret=False), [s((rows, BLOCK),
                                                          jnp.float32)]
    if name == "dequantize_q8":
        return partial(dequantize_q8, interpret=False), [
            s((rows, BLOCK), jnp.int8), s((rows,), jnp.float32)]
    if name == "quantize_f16":
        return partial(quantize_f16, interpret=False), [s((n,), jnp.float32)]
    if name == "dequantize_f16":
        return partial(dequantize_f16, interpret=False), [s((n,),
                                                             jnp.uint16)]
    k = int(name.removeprefix("fedavg_reduce_k"))
    return partial(fedavg_reduce, interpret=False), [
        s((k, n), jnp.float32), s((k,), jnp.float32)]


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("kernel", [
    "quantize_q8", "dequantize_q8", "quantize_f16", "dequantize_f16",
    "fedavg_reduce_k8", "fedavg_reduce_k256"])
def test_kernel_compiles_for_v5e(one_chip, kernel, n):
    fn, shapes = _kernel_case(kernel, n, one_chip)
    compiled = _compile(fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()   # Mosaic, not a fallback


def test_fedavg_k256_block_fits_scoped_vmem(one_chip):
    """The (K, TILE) f32 update block at K=256, double-buffered (4 MiB),
    is what the kernel's scoped VMEM holds, and that stays inside the
    limit Mosaic compiled it against."""
    fn, shapes = _kernel_case("fedavg_reduce_k256", SIZES[1], one_chip)
    text = _compile(fn, *shapes).as_text()
    kernel = next(ln for ln in text.splitlines() if "tpu_custom_call" in ln)
    used = re.search(r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                     kernel)
    assert used is not None, kernel[:2000]
    assert 2 * 256 * TILE * 4 <= int(used.group(1)) <= V5E_SCOPED_VMEM


def test_lenet5_client_grad_step_compiles_for_v5e(one_chip):
    """The jitted ``value_and_grad`` step an ``FLClient`` trains with, at
    batch 32, compiled for the chip."""
    from repro.core.params_codec import flatten_params
    from repro.data import synthetic_mnist
    from repro.fl import FLClient
    from repro.models import lenet5

    params = lenet5.init_params(jax.random.PRNGKey(0))
    _, spec = flatten_params(params)
    client = FLClient(client_id=0, data=synthetic_mnist(40, seed=0),
                      loss_fn=lenet5.loss_fn, spec=spec, batch_size=32)

    def on_chip(x):
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=one_chip)

    batch = {"images": jax.ShapeDtypeStruct((32, 28, 28, 1), jnp.float32,
                                            sharding=one_chip),
             "labels": jax.ShapeDtypeStruct((32,), jnp.int32,
                                            sharding=one_chip)}
    compiled = client._grad_fn.lower(jax.tree.map(on_chip, params),
                                     batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= lenet5.PARAM_COUNT * 4
