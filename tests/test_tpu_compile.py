"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a topology that
is described rather than present.  These tests compile the four Pallas
``availscan*`` kernels at the paper's width (1024 PEs) and the kernel's
budget edge (S = 2048 timeline records), ``availscan_select`` at the
CEA Curie thin-node width (80,640 PEs, twenty PE blocks, capacity 128),
and one ``admit_stream`` chunk step (64 requests) with and without the
kernel, at 1024 PEs and capacity 1024 and, on the kernel, at the Curie
shape.  Nothing runs; what the chip's compiler would refuse (tiling,
fast-memory limits, lowering) fails here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import batch as batch_lib
from repro.core import timeline as tl_lib
from repro.core.resources import ResourceSpec
from repro.core.types import ARRequest
from repro.kernels import availscan as _k

N_PE = 1024
S = 2048                 # kernel budget edge at 1024 PEs (ops.fits)
CAPACITY, CHUNK = 1024, 64
# the CEA Curie thin-node partition: 5,040 nodes x 16 cores
CURIE_PE, CURIE_CAPACITY = 80640, 128


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        # keep the compiler's logs out of the shared temp directory
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    # a described-chip program is written to the persistent cache but
    # cannot be read back without the chip: keep such compiles out
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(name, sh, s=S, n_pe=N_PE):
    i32, f32 = jnp.int32, jnp.float32
    p = 2 * s + 2            # candidate starts of an s-record timeline
    vec = functools.partial(_spec, (p,), i32, sh)
    rows = [_spec((s,), i32, sh), _spec((s,), i32, sh)]   # times, nxt
    live = _spec((p,), jnp.bool_, sh)
    words = _spec((s, tl_lib.n_words(n_pe)), jnp.uint32, sh)
    if name == "availscan":
        return (words, *rows, vec(), vec(), live), {}
    if name == "availscan_select":
        return (words, *rows, vec(), vec(), vec(),
                _spec((4,), i32, sh), live), {}
    # multi-resource: two planes of 512 units share the 1024-bit axis
    psel = _spec((n_pe, _k._LANE), f32, sh)
    if name == "availscan_mr":
        return (_spec((s, n_pe), f32, sh), psel, *rows, vec(), vec(),
                live), {}
    return (_spec((s, n_pe), f32, sh), psel, *rows, vec(), vec(), vec(),
            _spec((4,), i32, sh), live), {"n_res": 2}


@pytest.mark.parametrize("name,s,n_pe", [
    pytest.param("availscan", S, N_PE, id="availscan"),
    pytest.param("availscan_select", S, N_PE, id="availscan_select"),
    pytest.param("availscan_mr", S, N_PE, id="availscan_mr"),
    pytest.param("availscan_select_mr", S, N_PE,
                 id="availscan_select_mr"),
    pytest.param("availscan_select", CURIE_CAPACITY, CURIE_PE,
                 id="availscan_select-curie"),
])
def test_kernel_compiles_for_v5e(name, s, n_pe, one_chip,
                                 no_persistent_cache):
    args, static = _kernel_args(name, one_chip, s, n_pe)
    fn = getattr(_k, name)
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("use_kernel,capacity,n_pe", [
    pytest.param(False, CAPACITY, N_PE, id="jnp"),
    pytest.param(True, CAPACITY, N_PE, id="kernel"),
    pytest.param(True, CURIE_CAPACITY, CURIE_PE, id="kernel-curie"),
])
def test_admit_chunk_step_compiles_for_v5e(use_kernel, capacity, n_pe,
                                           one_chip,
                                           no_persistent_cache,
                                           monkeypatch):
    # the program resolves interpret mode from the default backend,
    # which is the CPU here: steer it to the compiled kernel, and drop
    # traces made under the CPU setting
    monkeypatch.setattr(_k, "_interpret_mode", lambda: False)
    jax.clear_caches()
    tile = 16 if use_kernel else None
    state = jax.eval_shape(lambda: tl_lib.init_state(
        capacity, n_pe, pending_capacity=capacity, index_tile=tile))
    reqs = [ARRequest(t_a=i, t_r=i, t_du=60, t_dl=i + 600, n_pe=32)
            for i in range(CHUNK)]
    batch = jax.eval_shape(lambda: batch_lib.requests_to_batch(reqs))
    place = lambda x: _spec(x.shape, x.dtype, one_chip)
    state, batch = jax.tree_util.tree_map(place, (state, batch))
    scalar = _spec((), jnp.int32, one_chip)
    compiled = batch_lib.admit_stream_donated.lower(
        state, batch, scalar, scalar, n_pe=n_pe, auto_release=True,
        use_kernel=use_kernel).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == use_kernel
    # the kernel reads the timeline packed: no [records, n_pe] matrix
    # of the occupancy's bits exists outside it
    assert not re.search(rf"\[{capacity},{n_pe}\]", text)
    jax.clear_caches()


def test_multi_resource_budget_matches_kernel_shape():
    # the MR compile above uses the widest plane layout the budget takes
    from repro.kernels import ops as kernel_ops
    rspec = ResourceSpec((512, 512))
    assert rspec.total_bits == N_PE
    assert kernel_ops.fits(S, N_PE, rspec)
    assert not kernel_ops.fits(2 * S, N_PE, rspec)
