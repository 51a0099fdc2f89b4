"""The program's trace names: host spans around each step of
``score_systems`` and the stream entry, and ``jax.named_scope`` names on
the device code of every streamed lowering.  A profiler trace reads
them on its own clock, so the benchmark can split host and device time
by layer without a second tracing system."""
import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core.quorum import QuorumSpec
from repro.frontier import score_systems
from repro.montecarlo import build_mask_table, streaming
from repro.montecarlo.regimes import gray_failure

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = jax.random.PRNGKey(3)
OFFS = jnp.array([0.0, 0.2], jnp.float32)
SYSTEMS = [QuorumSpec.paper_headline(11), QuorumSpec.fast_paxos(11),
           QuorumSpec(11, 7, 5, 9)]
SCOPES = ("repro.sample", "repro.decide", "repro.sketch")

# child -> parent, as the calls nest
SPAN_TREE = {
    "repro.score.table": "repro.score",
    "repro.score.masks": "repro.score.table",
    "repro.stream.fast_path": "repro.score",
    "repro.stream.race": "repro.score",
    "repro.score.readback": "repro.score",
    "repro.score.frontier": "repro.score",
}


def _host_spans(log_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns,
                             dict(e.stats)))
    return spans


def test_score_systems_emits_the_span_tree(tmp_path):
    chunk = 256
    kw = dict(trials=2 * chunk, chunk=chunk, shard=False, seed=11)
    score_systems(SYSTEMS, **kw)                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        score_systems(SYSTEMS, **kw)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    assert set(spans) == {"repro.score", *SPAN_TREE}
    assert all(len(v) == 1 for v in spans.values()), spans
    (_, _, args), = spans["repro.score"]
    assert (args["systems"], args["trials"], args["seed"]) == (3, 2 * chunk,
                                                               11)
    for child, parent in SPAN_TREE.items():
        (cs, ce, _), = spans[child]
        (ps, pe, _), = spans[parent]
        assert ps <= cs and ce <= pe, (child, parent)
    order = ["repro.score.table", "repro.stream.fast_path",
             "repro.stream.race", "repro.score.readback",
             "repro.score.frontier"]
    starts = [spans[n][0][0] for n in order]
    ends = [spans[n][0][1] for n in order]
    assert starts == sorted(starts)
    assert all(e <= s for e, s in zip(ends, starts[1:]))


def _stream_text(monkeypatch, call):
    """The lowered text, with its name stacks, of every ``_stream`` program
    ``call`` dispatches."""
    real, texts = streaming._stream, []

    def lower_only(*a, **k):
        texts.append(real.lower(*a, **k).as_text(debug_info=True))
    monkeypatch.setattr(streaming, "_stream", lower_only)
    call()
    assert texts
    return "\n".join(texts)


CARD = build_mask_table(SYSTEMS)
MASKED = build_mask_table(SYSTEMS, specialize=False)
REGIMES = gray_failure(11, epoch_trials=512)
PATHS = {
    "card_race": lambda: streaming.race_stream(
        KEY, CARD, OFFS, n=11, k_proposers=2, trials=4096, chunk=1024,
        shard=False),
    "card_fast_path": lambda: streaming.fast_path_stream(
        KEY, CARD, n=11, trials=4096, chunk=1024, shard=False),
    "card_classic_path": lambda: streaming.classic_path_stream(
        KEY, CARD, n=11, trials=4096, chunk=1024, shard=False),
    "masked_race": lambda: streaming.race_stream(
        KEY, MASKED, OFFS, n=11, k_proposers=2, trials=4096, chunk=1024,
        shard=False),
    "masked_fast_path": lambda: streaming.fast_path_stream(
        KEY, MASKED, n=11, trials=4096, chunk=1024, shard=False),
    "fused_kernel_race": lambda: streaming.race_stream(
        KEY, MASKED, OFFS, n=11, k_proposers=2, trials=4096, chunk=1024,
        shard=False, use_kernel=True),
    "regime_race": lambda: streaming.race_stream(
        KEY, CARD, OFFS, n=11, k_proposers=2, trials=4096, chunk=1024,
        shard=False, regimes=REGIMES),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_stream_programs_carry_the_layer_scopes(monkeypatch, path):
    text = _stream_text(monkeypatch, PATHS[path])
    for scope in SCOPES:
        assert scope in text, (path, scope)
    assert "repro.merge" not in text                # one device: no merge
    if path == "fused_kernel_race":
        assert "stream_tally_decide_hist" in text


MESH_SCRIPT = r"""
import json
import jax, jax.numpy as jnp
from repro.core.quorum import QuorumSpec
from repro.montecarlo import build_mask_table, streaming
assert len(jax.devices()) == 4
real, texts = streaming._stream, []
streaming._stream = lambda *a, **k: texts.append(
    real.lower(*a, **k).as_text(debug_info=True))
card = build_mask_table([QuorumSpec.paper_headline(11),
                         QuorumSpec.fast_paxos(11)])
masked = build_mask_table([QuorumSpec.paper_headline(11)], specialize=False)
for table in (card, masked):
    streaming.race_stream(jax.random.PRNGKey(0), table,
                          jnp.array([0.0, 0.2], jnp.float32), n=11,
                          k_proposers=2, trials=8192, chunk=1024, shard=True)
print(json.dumps([{s: s in t for s in ("repro.sample", "repro.decide",
                                       "repro.sketch", "repro.merge")}
                  for t in texts]))
"""


def test_merge_scope_on_a_four_device_trial_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    found = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(found) == 2
    assert all(all(f.values()) for f in found), found


CACHE_SCRIPT = r"""
import json
import jax, jax.numpy as jnp
from repro import compile_cache
compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
hits = []
jax.monitoring.register_event_listener(
    lambda e, **_: hits.append(e)
    if e == "/jax/compilation_cache/cache_hits" else None)
x = jnp.arange(8.0)
x.block_until_ready()

def make(scope):
    def f(x):
        with jax.named_scope(scope):
            return jnp.sin(x) * 2
    return jax.jit(f)

out = []
for scope in ("repro.sample", "repro.sample", "repro.sketch"):
    n = len(hits)
    make(scope)(x).block_until_ready()
    out.append(len(hits) - n)
print(json.dumps(out))
"""


def test_a_scope_only_change_is_not_read_back_from_the_compile_cache(
        tmp_path):
    """The persistent cache keys on debug metadata: an executable compiled
    under other scope names is never handed back, so a trace always shows
    the names the code has."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", CACHE_SCRIPT], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == [0, 1, 0]
