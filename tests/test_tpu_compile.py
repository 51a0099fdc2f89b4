"""Compile the quorum_tally Pallas kernels for a described TPU v5e.

Interpret-mode parity (tests/test_kernels.py) cannot see what the chip's
compiler refuses: block shapes off the (8, 128) tiling, fast-memory
limits, reductions Mosaic cannot lower.  These tests compile each kernel
ahead of time, ``interpret=False``, for one chip of a described
``v5e:2x2`` topology at the widths users run: the n=11 sweep chunk and the
n=12 mixed-family frontier chunk.  Nothing runs; a passing compile is not a
chip run.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.quorum_tally import kernel
from repro.montecarlo import engine, streaming

f32, i32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_tally_decide_compiles_for_v5e(one_chip):
    txt = _compiled_text(lambda v, q: kernel.tally_decide(v, 2, q, False),
                         one_chip, ((16_384, 11), i32), ((), i32))
    assert "tpu_custom_call" in txt


def test_masked_tally_compiles_for_v5e(one_chip):
    txt = _compiled_text(
        lambda v, w, t: kernel.masked_tally(v, w, t, 2, False), one_chip,
        ((16_384, 11), i32), ((12, 11), f32), ((12,), f32))
    assert "tpu_custom_call" in txt


# (chunk S, cluster n, proposers K, systems M, mask rows G, k_sat):
# the n=11 sweep chunk, and the n=12 grid + weighted + cardinality batch
# at its own saturation depths (engine.saturation_depths -> (12, 12, 12)).
MEGAKERNEL_SHAPES = {
    "sweep": (16_384, 11, 2, 271, 1, (6, 6, 9)),
    "frontier": (8_192, 12, 3, 13, 12, (12, 12, 12)),
}


@pytest.mark.parametrize("shape", sorted(MEGAKERNEL_SHAPES))
def test_stream_megakernel_compiles_for_v5e(one_chip, shape):
    S, n, K, M, G, k_sat = MEGAKERNEL_SHAPES[shape]
    precision = streaming.DEFAULT_PRECISION

    def fn(*a):
        return kernel.stream_tally_decide_hist(
            *a, n_values=K, k_sat=k_sat, precision=precision,
            bins=streaming.sketch_bins(precision),
            undecided_ms=float(engine.UNDECIDED_MS), interpret=False)

    masks = [((M, G, n), f32), ((M, G), f32)] * 3
    txt = _compiled_text(fn, one_chip, ((S, n), i32), ((S, K, n), f32),
                         ((S, n), f32), ((S, n), f32), *masks,
                         ((S,), jnp.bool_))
    assert "tpu_custom_call" in txt
