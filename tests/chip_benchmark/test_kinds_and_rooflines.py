"""What lets the next architecture into the benchmark without an edit: a model
kind is a file found by name (one the tree lacks is added wholly under a
temporary directory and served at a tiny size on the CPU), the serve check
takes its logits from the kind and its rule from the cell, the traced slice's
engine records reach a reader with their attributes, and a kernel's roofline
is a reader with its own count of the least work, held here to hand counts on
``fixtures/v5e_named_kernels_sample.json``.

Nothing here is a device number measured by the test: the fixture's times were
recorded on the chip, the slice records beside them are made to match."""

import ast
import collections
import glob
import hashlib
import json
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from accelerate_tpu.telemetry import tracing  # noqa: E402
from benchmarks.chip import harness, models, program_spans, roofline, trace_reduce  # noqa: E402
from benchmarks.chip.runners import serve  # noqa: E402

CHIP = os.path.join(REPO, "benchmarks", "chip")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
KIND_FILES = sorted(glob.glob(os.path.join(CHIP, "kinds", "*.py")))

# ------------------------------------------------- a kind the tree does not have

TIED_KIND = '''
"""A decoder whose output head is its embedding table, transposed: a kind the
tree lacks. The program's side is its LlamaConfig with ``tie_embeddings``; the
reference is this file's own, in numpy and float64, from the equations."""

import numpy as np

from accelerate_tpu import models as m

init = m.init_llama


def program_config(c, *, n_layers, max_seq_len):
    return m.LlamaConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_attention_heads"],
        ffn_dim=c["intermediate_size"], max_seq_len=max_seq_len, rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"], tie_embeddings=True)


def forward_flops_per_token(c, seq_len, n_layers):
    d, f = c["hidden_size"], c["intermediate_size"]
    return n_layers * (2 * (4 * d * d + 3 * d * f) + 4 * d * (seq_len + 1) / 2) + 2 * d * c["vocab_size"]


def _norm(x, scale, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    T, _, dh = x.shape
    angle = np.arange(T)[:, None] / theta ** (np.arange(0, dh, 2) / dh)[None]
    cos, sin = np.cos(angle)[:, None], np.sin(angle)[:, None]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return np.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def reference_logits(c):
    H, eps, theta = c["num_attention_heads"], c["rms_norm_eps"], c["rope_theta"]

    def logits(params, ids):
        import jax

        p = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float64), params)
        ids = np.asarray(ids)
        T = ids.size
        h = p["embed_tokens"]["embedding"][ids]
        for i in range(p["layers"]["wq"]["kernel"].shape[0]):
            lp = jax.tree_util.tree_map(lambda x: x[i], p["layers"])
            x = _norm(h, lp["attn_norm"]["scale"], eps)
            q, k, v = ((x @ lp[w]["kernel"]).reshape(T, H, -1) for w in ("wq", "wk", "wv"))
            q, k = _rope(q, theta), _rope(k, theta)
            s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
            s = np.where(np.tril(np.ones((T, T), bool))[None], s, -np.inf)
            w = np.exp(s - s.max(-1, keepdims=True))
            ctx = np.einsum("hqk,khd->qhd", w / w.sum(-1, keepdims=True), v).reshape(T, -1)
            h = h + ctx @ lp["wo"]["kernel"]
            x = _norm(h, lp["mlp_norm"]["scale"], eps)
            gate = x @ lp["w1"]["kernel"]
            h = h + (gate / (1 + np.exp(-gate)) * (x @ lp["w3"]["kernel"])) @ lp["w2"]["kernel"]
        return (_norm(h, p["final_norm"]["scale"], eps) @ p["embed_tokens"]["embedding"].T).astype(np.float32)

    return logits
'''

TIED_METRIC = '''
"""Positions the serve check compared, from the cell's own file (the reader reaches the cell)."""


def read(record):
    return record.cell.spec["check"]["requests"] if record.cell else None
'''

TIED_FILES = {
    "BENCHMARK.json": {
        "command": ["python3", "benchmarks/chip/run.py"], "paths": ["benchmarks/chip"],
        "run_seconds": 1,
        "configs": [{"name": "tied-tiny", "source": "none: a test's own", "reduced": [], "why": "a test",
                     "file": "benchmarks/chip/configs/tied-tiny.json"}],
        "workloads": [{"name": "tied-tiny.burst", "config": "tied-tiny", "traffic": "burst",
                       "chips": 1, "why": "a test"}],
        "end_to_end": [
            {"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.1,
             "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"}],
        "per_layer": [{"name": "checked_requests.serve", "unit": "requests", "better": "higher",
                       "source": "program_counter", "layer": "benchmark", "moves": "serve_tokens_per_s"}],
    },
    "benchmarks/chip/configs/tied-tiny.json": {
        "kind": "tied", "vocab_size": 384, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 128, "rms_norm_eps": 1e-5, "rope_theta": 1e4},
    "benchmarks/chip/traffic/burst.json": {
        "prompt_len": [8, 40], "output_len": [4, 12], "block": 8, "sizes_seed": 0,
        "arrival": {"kind": "at_zero", "n_requests": 60}},
    "benchmarks/chip/workloads/tied-tiny.burst.json": {
        "config": "tied-tiny", "traffic": "burst", "runner": "serve", "chips": 1, "dtype": "f32",
        "engine": dict(max_slots=4, block_size=16, num_blocks=33, max_seq_len=64, slot_buckets=[2, 4],
                       block_buckets=[2, 4], prefill_buckets=[16, 32], admit_watermark_blocks=2),
        "drain_s": 5,
        # float32 on both sides: the engine's token is the float64 reference's argmax
        "check": {"requests": 4, "max_tokens": 64, "margin": 1e-3, "agreement": 0.99}},
    "benchmarks/chip/kinds/tied.py": TIED_KIND,
    "benchmarks/chip/layer_metrics/checked_requests.serve.py": TIED_METRIC,
}


def _digest_of_the_benchmarks_files() -> str:
    digest = hashlib.sha256()
    for path in sorted(glob.glob(os.path.join(CHIP, "**", "*"), recursive=True)):
        if os.path.isfile(path) and "__pycache__" not in path:
            digest.update(path.encode() + open(path, "rb").read())
    return digest.hexdigest()


def test_a_kind_the_tree_lacks_is_added_wholly_as_files_under_another_directory(tmp_path):
    """A kind file with its own reference, a configuration, a traffic mix, a
    cell, a per-layer metric and a manifest, all under a temporary directory:
    ``load_cell`` finds them from the manifest's place, the serve runner takes
    the kind's program config, init and reference through the hooks, and no
    file of ``benchmarks/chip/`` is touched."""
    before = _digest_of_the_benchmarks_files()
    for name, content in TIED_FILES.items():
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(content if isinstance(content, str) else json.dumps(content))
    cell = harness.load_cell("tied-tiny.burst", manifest_path=str(tmp_path / "BENCHMARK.json"))
    assert cell.root == str(tmp_path / "benchmarks" / "chip") and cell.config["kind"] == "tied"
    record = serve.run(cell, seed=2147483659, seconds=1.0, trace=False, process_t0=0.0,
                       allow_cpu=True)
    check = record.facts["check"]
    assert record.correct and check["ok"] and check["requests"] == 4, record.facts
    assert check["positions"] >= 16 and check["max_margin_deviations"] < 1e-3
    assert record.compared["margin_deviations"] == (check["margin_at_quantile"], 1e-3)
    line = harness.result_line(cell, record, traced=True)  # the reader is the directory's own
    assert line["metrics"] == {"checked_requests.serve": {"value": 4.0, "unit": "requests"}}
    assert list(line)[-1] == "compared" and line["compared"]["late_compiles"] == {"value": 0, "limit": 0}
    assert _digest_of_the_benchmarks_files() == before
    # a kind is looked for where the cell's files are, and a missing one names what is there
    with pytest.raises(KeyError, match=r"no model kind 'llama'.*the kinds there are \['tied'\]"):
        models.kind_of({"kind": "llama"}, cell.root)
    with pytest.raises(KeyError, match=r"no model kind 'tied'.*\['bert', 'llama'\]"):
        models.kind_of(cell.config)  # the tree itself still lacks it


def test_a_missing_hook_names_the_file_and_what_the_hook_is():
    bert = models.kind_of({"kind": "bert"})
    assert {"program_config", "init", "loss", "shard_rules", "forward_flops_per_token",
            "reference_loss"} == set(bert)
    with pytest.raises(KeyError, match=r"kinds/bert\.py has no `reference_logits` \(serve:"):
        bert["reference_logits"]
    assert set(models.kind_of({"kind": "llama"})) == set(models.HOOKS)


@pytest.mark.parametrize("path", KIND_FILES, ids=[os.path.basename(p) for p in KIND_FILES])
def test_a_kinds_reference_imports_nothing_of_the_program(path):
    """A kind file names its reference module as ``reference``; that module
    may import jax and numpy and nothing of ``accelerate_tpu``, whose weights
    it is handed and whose arithmetic it judges."""
    kind = models.kind_of({"kind": os.path.basename(path)[:-3]})
    tree = ast.parse(open(kind.module.reference.__file__).read())
    imported = [alias.name for node in ast.walk(tree) if isinstance(node, ast.Import)
                for alias in node.names]
    imported += [node.module or "" for node in ast.walk(tree) if isinstance(node, ast.ImportFrom)]
    assert imported and not [name for name in imported if name.split(".")[0] == "accelerate_tpu"]


# ------------------------------------------------------------ the serve check


def _finished(prompt, generated):
    request = types.SimpleNamespace(
        prompt=np.asarray(prompt, np.int32), generated=list(generated),
        output_ids=lambda: np.asarray(list(prompt) + list(generated), np.int32))
    return types.SimpleNamespace(left="finished", request=request)


@pytest.mark.parametrize("quantile", [None, 90, 50])
def test_check_holds_the_largest_margin_or_the_cells_percentile_of_them(quantile):
    """Two requests of 6 + 4 generated tokens over a vocabulary of 4, the
    reference's logits fixed: token 0 always has logit 1 and the others 0,
    -1, -2, so the deviation is sqrt(1.25) everywhere and a served token t
    lies t' = (0, 1, 2, 3)[t] logits under the best. Margins in deviations:
    ten positions, served tokens 0 0 0 0 0 1 | 0 0 2 0 -> eight zeros, one
    1/sqrt(1.25) = 0.894 and one 2/sqrt(1.25) = 1.789. The largest is 1.789
    (today's rule); the 90th percentile by nearest rank is the 9th of ten,
    0.894; the 50th is 0."""
    logits = np.tile(np.array([1.0, 0.0, -1.0, -2.0], np.float32), (16, 1))
    kind = {"reference_logits": lambda c: (lambda params, ids: logits)}
    check = {"requests": 2, "max_tokens": 16, "margin": 1.0, "agreement": 0.8}
    if quantile is not None:
        check["margin_quantile"] = quantile
    cell = types.SimpleNamespace(config={}, spec={"check": check})
    trackers = [_finished([3, 3], [0, 0, 0, 0, 0, 1]), _finished([3, 3, 3], [0, 0, 2, 0])]
    result = serve._check(cell, kind, None, trackers, seed=5)
    dev = np.sqrt(1.25)
    assert result["requests"] == 2 and result["positions"] == 10
    assert result["max_margin_deviations"] == pytest.approx(2 / dev)
    assert result["argmax_agreement"] == pytest.approx(0.8)
    assert result["margin_quantile"] == (quantile or 100)
    expected = {None: 2 / dev, 90: 1 / dev, 50: 0.0}[quantile]
    assert result["margin_at_quantile"] == pytest.approx(expected)
    assert result["ok"] is bool(expected <= 1.0)  # absent: today's verdict, the largest decides
    assert {"margin_allowed", "agreement_required"} <= set(result)


# ------------------------------------------- the slice's records, by the engine


def test_slice_steps_hands_out_the_attributes_a_tiny_engine_wrote(monkeypatch):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import LlamaConfig, init_llama
    from accelerate_tpu.serving import BucketLattice, ServingEngine

    monkeypatch.setattr(tracing, "_RING", collections.deque(maxlen=4096))
    cfg = LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
                      max_seq_len=96, rope_theta=1e4, norm_eps=1e-5)
    engine = ServingEngine(init_llama(cfg, jax.random.PRNGKey(0)), cfg, num_blocks=33,
                           block_size=16, max_slots=2, cache_dtype=jnp.float32,
                           lattice=BucketLattice((2,), (6,), (16, 32)))
    rng = np.random.default_rng(0)
    first = engine.submit(rng.integers(0, 256, 40).astype(np.int32), 6)
    for _ in range(3):
        engine.step()
    second = engine.submit(rng.integers(0, 256, 9).astype(np.int32), 6)  # admitted in step 3
    while not engine.scheduler.idle():
        engine.step()
    record = harness.Record(True, 0, 0, {}, {"steps": 3, "slice_steps": [3, engine.steps]}, {},
                            trace={"device_ops": []})
    window, sliced = program_spans.window_steps(record), program_spans.slice_steps(record)
    assert sorted(window) == [0, 1, 2] and sorted(sliced) == list(range(3, engine.steps))
    (prefill,) = program_spans.attributes(window, "prefill")
    assert (prefill["rid"], prefill["tokens"], prefill["cached"]) == (first.rid, 40, 0)
    (prefill,) = program_spans.attributes(sliced, "prefill")
    assert (prefill["rid"], prefill["tokens"]) == (second.rid, 9)
    builds = program_spans.attributes(window, "build") + program_spans.attributes(sliced, "build")
    assert all({"live_blocks", "batch", "slot_bucket", "block_bucket"} <= set(b) for b in builds)
    assert builds[0]["batch"] == 1 and builds[3]["batch"] == 2  # the second row joined in step 3
    assert sum(b["live_blocks"] for b in builds) == engine.stats()["decode_blocks_live"]
    assert program_spans.total(sliced[3], "build") > 0.0
    # no trace, no slice; and a slice the ring no longer holds whole is nothing, not a part
    assert program_spans.slice_steps(harness.Record(True, 0, 0, {}, record.clocks, {})) is None
    record.clocks["slice_steps"] = [3, engine.steps + 1]
    assert program_spans.slice_steps(record) is None


# ------------------------------------------------------- the kernels' rooflines

ROOFLINES = ("paged_decode_roofline.serve", "paged_prefill_roofline.serve")


@pytest.fixture(scope="module")
def named_trace():
    sample = json.load(open(os.path.join(FIXTURES, "v5e_named_kernels_sample.json")))
    reduced = trace_reduce.reduce(
        [[tuple(op) for op in sample["device_ops"]]], [tuple(a) for a in sample["annotations"]])
    return sample, reduced


def _slice_ring(builds=1):
    """The fixture's stretch is one prefill chunk's program and the decode
    program behind it, 16 layers each: step 5 of engine 7 prefilled 384 tokens
    of a prompt with nothing cached and decoded 18 rows holding 1015 blocks."""
    ring = collections.deque(maxlen=64)
    key = dict(engine=7, step=5)
    ring.append(("atpu.serve.prefill", 0, 60_000_000, dict(key, rid=1, tokens=384, cached=0)))
    for _ in range(builds):
        ring.append(("atpu.serve.build", 0, 1_000_000,
                     dict(key, batch=18, slot_bucket=32, block_bucket=144, live_blocks=1015)))
    ring.append(("atpu.serve.step", 0, 130_000_000, key))
    return ring


def _slice_record(trace, cell="mistral-7b.chat-sat"):
    clocks = {"steps": 5, "slice_steps": [5, 6], "device_kind": "TPU v5 lite", "chips": 1}
    return harness.Record(True, 0, 0, {}, clocks, {}, trace=trace,
                          cell=cell and harness.load_cell(cell))


def test_trace_reduction_counts_calls_by_short_name(named_trace):
    sample, reduced = named_trace
    calls = reduced["device_op_calls"]
    assert set(calls) == {name for name, _ in reduced["device_ops"]}
    for mark in ("paged_decode", "paged_prefill"):
        assert sum(n for name, n in calls.items() if mark in name) == sample["expect"][mark + "_calls"]


@pytest.mark.parametrize("name,hand_count", [
    # 16 layers x 2 bytes x (1015 blocks x 16 tokens x 8 heads x 128 x 2 (K, V)
    #   + 18 rows x 32 heads x 128 x 2 (q, out)) = 1 069 023 232 bytes: 1.3053 ms at
    # 819 GB/s; its operations, 16 x 4 x 16 240 tokens x 4096 = 4.26 G, need 0.0216 ms
    ("paged_decode_roofline.serve", (1_069_023_232 / 819e9, "paged_decode_s")),
    # one chunk of 384 behind nothing: 16 x 2 x (384 x 1024 x 2 + 384 x 4096 x 2) =
    # 125 829 120 bytes, 0.15364 ms; 384 x 385 / 2 = 73 920 causal pairs x 16 x 4 x
    # 4096 = 19.38 G operations, 0.09836 ms at 197 T/s: the bytes bind
    ("paged_prefill_roofline.serve", (125_829_120 / 819e9, "paged_prefill_s")),
])
def test_roofline_reader_against_a_hand_count_on_the_named_kernels_sample(
        monkeypatch, named_trace, name, hand_count):
    sample, reduced = named_trace
    least_s, seconds_key = hand_count
    monkeypatch.setattr(tracing, "_RING", _slice_ring())
    value = harness.layer_metric_reader(name)(_slice_record(reduced))
    assert value == pytest.approx(100.0 * least_s / sample["expect"][seconds_key], rel=1e-9)
    assert 0.0 < value < 100.0


@pytest.mark.parametrize("name", ROOFLINES)
def test_roofline_reader_reads_nothing_where_records_and_trace_do_not_match(
        monkeypatch, named_trace, name):
    """None, never 0 and never a share of the wrong steps: no trace, no ring,
    no cell, a trace without the kernel's name, and a trace whose calls are
    not one a layer for each record (two decode batches or two chunks
    recorded, one program traced)."""
    _, reduced = named_trace
    read = harness.layer_metric_reader(name)
    monkeypatch.setattr(tracing, "_RING", _slice_ring())
    assert read(_slice_record(reduced)) is not None
    assert read(_slice_record(None)) is None
    assert read(_slice_record(reduced, cell=None)) is None
    unnamed = dict(reduced, device_ops=[["closed_call.4", 0.3], ["fusion.1 kLoop", 0.3]])
    assert read(_slice_record(unnamed)) is None
    two = _slice_ring(builds=2)
    two.append(("atpu.serve.prefill", 0, 1, dict(engine=7, step=5, rid=2, tokens=20, cached=0)))
    monkeypatch.setattr(tracing, "_RING", two)
    assert read(_slice_record(reduced)) is None
    monkeypatch.setattr(tracing, "_RING", collections.deque(maxlen=8))
    assert read(_slice_record(reduced)) is None


def test_a_prefill_is_counted_in_chunks_of_the_largest_bucket():
    chunks = harness.layer_metric_reader("paged_prefill_roofline.serve").__globals__["chunks"]
    assert list(chunks(1200, 64, 512)) == [(64, 512), (576, 512), (1088, 176)]
    assert list(chunks(512, 0, 512)) == [(0, 512)] and list(chunks(0, 30, 512)) == []


def test_share_of_the_roofline_on_the_published_peaks():
    # 819 MB at 819 GB/s is 1 ms, 197 G operations at 197 T/s is 1 ms: the larger binds
    assert roofline.share_percent(4e-3, 819e6, 0.0, "TPU v5 lite") == pytest.approx(25.0)
    assert roofline.share_percent(4e-3, 819e6, 2 * 197e9, "TPU v5 lite") == pytest.approx(50.0)
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.share_percent(1.0, 1.0, 1.0, "cpu")
