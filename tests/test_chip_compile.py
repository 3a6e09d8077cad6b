"""Compile the served programs for a TPU v5e chip, without the chip.

The streaming megakernels and the pre-decode kernel are compiled for a
*described* ``v5e:2x2`` topology at the widths ``chip_smoke.py`` serves:
10,000 profiles over the 24-tag DTD (a ~9,000-state plan of many
blocks), batches of 64 documents in 68 KB rows, and a match buffer
that holds every (document, accept state) pair.  The bytes kernels
compile besides at the benchmark's ``xmark-1k`` widths: 81,920-byte
rows in batches of 16 and 64, and the sparse one at ``xmark-10m``'s
10.5 MiB rows in batches of 1 and 4.  Mosaic refuses here
what it would refuse on the chip — block shapes off the tiling, gathers
it cannot lower, more fast memory than a kernel may use — at no chip
time.  Nothing runs, so this says nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engines
from repro.core.dictionary import TagDictionary
from repro.core.engines import streaming
from repro.core.nfa import compile_queries
from repro.data.generator import DTD, gen_profiles
from repro.kernels.predecode import predecode_pallas

BATCH = 64
ROW_BYTES = 68 * 1024
EVENTS = 8192
#: the ``xmark-1k`` cell's row (``byte_bucket``) and batch sizes
XMARK_ROW_BYTES = 81_920
XMARK_BATCHES = (16, 64)
#: the ``xmark-10m`` cell's row: 10.5 MiB, 2,688 chunks of 4,096 bytes
XMARK10M_ROW_BYTES = 11_010_048
XMARK10M_BATCHES = (1, 4)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        # a compile for a described chip cannot be read back from the
        # persistent cache here: keep it out of the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure to describe
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def plan():
    """The smoke's deployment plan: many blocks, ~9,000 states."""
    dtd = DTD.generate(n_tags=24, seed=0)
    d = TagDictionary()
    dtd.register(d)
    profiles = [q for length in range(2, 7)
                for q in gen_profiles(dtd, n=2000, length=length,
                                      p_wild=0.1, p_desc=0.3,
                                      seed=length)]
    nfa = compile_queries(profiles, d, shared=True)
    eng = engines.create("streaming", nfa, dictionary=d, kernel="pallas")
    assert eng.plan_.meta["n_blocks"] > 1
    assert eng.plan_.meta["n_states"] > 8000
    return eng.plan_, int(np.unique(nfa.tables.accept_state).size)


def _shape(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _program(name, plan, per_doc, one_chip, batch=BATCH,
             row_bytes=ROW_BYTES):
    """(jitted program, argument shapes) of one served launch; the
    match buffer holds ``per_doc`` entries per document."""
    p = jax.tree.map(lambda x: _shape(x, one_chip), plan)
    g, qb = plan["kb_acc_word"].shape

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cap = batch * per_doc
    data, starts = s((batch, row_bytes), jnp.uint8), s((batch, 2))
    ids, lanes = s((batch, 1)), s((g, qb))
    kind, tag = s((batch, EVENTS)), s((batch, EVENTS))
    return {
        "bytes-sparse": lambda: streaming._run_bytes_fused_sparse.lower(
            p, data, starts, ids, lanes, cap=cap, interpret=False),
        "bytes-dense": lambda: streaming._run_bytes_fused.lower(
            p, data, starts, interpret=False),
        "events-sparse": lambda: streaming._run_batch_kernel_fused.lower(
            p, kind, tag, ids, lanes, cap=cap, interpret=False),
        "events-dense": lambda: streaming._run_batch_kernel.lower(
            p, kind, tag, interpret=False),
        "predecode": lambda: jax.jit(
            lambda x: predecode_pallas(x, interpret=False)).lower(data),
    }[name]()


@pytest.mark.parametrize("name", ["bytes-sparse", "bytes-dense",
                                  "events-sparse", "events-dense",
                                  "predecode"])
def test_compiles_for_v5e(name, plan, one_chip):
    plan_, per_doc = plan
    compiled = _program(name, plan_, per_doc, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", XMARK_BATCHES)
@pytest.mark.parametrize("name", ["bytes-sparse", "bytes-dense"])
def test_bytes_kernels_compile_at_xmark_widths(name, batch, plan, one_chip):
    """The tag-start bitmap input and the scalar walk over its words, at
    the rows and batches the benchmark's cells launch."""
    plan_, per_doc = plan
    compiled = _program(name, plan_, per_doc, one_chip, batch=batch,
                        row_bytes=XMARK_ROW_BYTES).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", XMARK10M_BATCHES)
def test_bytes_kernel_streams_whole_10mb_rows(batch, plan, one_chip):
    """A 10.5 MiB row: the bytes stay in HBM and reach the scalar core
    one chunk at a time, so no buffer of fast memory grows with the row
    and a batch of several such rows compiles."""
    plan_, per_doc = plan
    compiled = _program("bytes-sparse", plan_, per_doc, one_chip,
                        batch=batch, row_bytes=XMARK10M_ROW_BYTES).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name,kernel", [
    ("bytes-sparse", "stream_filter_bytes_pallas_sparse"),
    ("bytes-dense", "stream_filter_bytes_pallas"),
    ("events-sparse", "stream_filter_pallas_sparse"),
    ("events-dense", "stream_filter_pallas")])
def test_megakernel_keeps_its_name(name, kernel, plan, one_chip):
    """The trace names a kernel's ``XLA Ops`` event after the custom
    call, whose name is the ``pallas_call``'s own ``name=``: the
    benchmark's trace reduction finds the kernel by it."""
    plan_, per_doc = plan
    text = _program(name, plan_, per_doc, one_chip).compile().as_text()
    assert re.search(rf"%{kernel}(\.\d+)? = .* custom-call\(", text)
