"""Compile the quorum_tally Pallas kernels, and the streamed race pass,
for a described TPU v5e.

Interpret-mode parity (tests/test_kernels.py) cannot see what the chip's
compiler refuses: block shapes off the (8, 128) tiling, fast-memory
limits, reductions Mosaic cannot lower.  These tests compile each kernel
ahead of time, ``interpret=False``, for one chip of a described
``v5e:2x2`` topology at the widths users run: the n=11 sweep chunk and the
n=12 mixed-family frontier chunk.  The race pass's ``_stream`` program is
compiled the same way, to see which lowering the TPU takes for its sketch
histograms.  Nothing runs; a passing compile is not a chip run.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.quorum import all_valid_specs
from repro.kernels.quorum_tally import kernel
from repro.montecarlo import engine, streaming
from repro.montecarlo.latency import default_delay

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


# Temp bytes of the race program below when both sketch histograms were
# scatter-adds, and what the one-hot contractions may add to them.
SCATTER_RACE_TEMP_BYTES = 135_249_920
TEMP_MARGIN_BYTES = 8 << 20


def test_race_stream_histograms_contract_on_the_mxu(one_chip):
    """The benchmark's race pass (all 271 FFP triples at n=11, 2 proposers,
    chunk 8,192): on the TPU both slot histograms are one-hot contractions
    under ``slot_hist``, no scatter writes either flat histogram, and the
    one-hots never reach HBM (temp bytes as with the scatters)."""
    n, chunk, trials = 11, 8_192, 10 ** 6
    precision = streaming.DEFAULT_PRECISION
    table = engine.build_mask_table(list(all_valid_specs(n)))
    k_sat = streaming._resolve_k_sat(table, "auto", n)
    layout = streaming._card_layout(table, "coordinated")

    def spec(x):
        x = jnp.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    args = jax.tree_util.tree_map(spec, (
        jax.random.PRNGKey(0), table, layout,
        0.2 * jnp.arange(2, dtype=f32), default_delay(), jnp.int32(trials)))
    compiled = streaming._stream.lower(
        *args, None, path="race", n=n, k_proposers=2, chunk=chunk,
        n_chunks=-(-trials // chunk), n_epochs=1, precision=precision,
        use_kernel=False, mesh=None, k_sat=k_sat,
        recovery="coordinated").compile()
    lines = compiled.as_text().splitlines()

    pairs, k2f = layout[0].shape[0], k_sat[2]
    V, B = k2f + 1, streaming.sketch_bins(precision)
    assert (pairs, V, B) == (66, 12, 1038)
    for flat in (pairs * (V + 1) * (B + 1), k2f * (V + 1) * B):
        assert not [l for l in lines if f"s32[{flat}]" in l
                    and "scatter" in l], flat
    convs = [l for l in lines if "convolution(" in l and "/slot_hist/" in l]
    assert len(convs) == 2, convs
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= SCATTER_RACE_TEMP_BYTES + TEMP_MARGIN_BYTES, temp
