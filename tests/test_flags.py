"""CLI flag materialization: --preset folds BASELINE configs into unset
flags; explicitly-passed flags always win. Runs in subprocesses because absl
flags are process-global (a second define_flags() would collide)."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

_SNIPPET = """
import sys
from absl import flags
from transformer_tpu.cli.flags import (
    define_flags, flags_to_model_config, flags_to_train_config,
)
define_flags()
flags.FLAGS(sys.argv)
m = flags_to_model_config(100, 100)
t = flags_to_train_config()
print(m.num_layers, m.d_model, m.dff, m.num_heads, m.tie_embeddings,
      m.decoder_only, m.attention_impl, t.label_smoothing, t.sequence_length,
      t.batch_size)
"""


def _run(snippet: str, *argv: str) -> str:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-c", snippet, *argv],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip()


def _materialize(*argv: str) -> list[str]:
    return _run(_SNIPPET, *argv).split()


def test_no_preset_keeps_reference_defaults():
    vals = _materialize()
    assert vals == [
        "4", "512", "1024", "4", "False", "False", "xla", "0.0", "50", "64"
    ]


def test_preset_big_applies():
    vals = _materialize("--preset=big")
    assert vals[:4] == ["6", "1024", "4096", "16"]
    assert vals[7] == "0.1"  # label smoothing comes with the big config
    assert vals[9] == "32"  # and the benchmark's batch size


def test_explicit_flag_beats_preset():
    vals = _materialize("--preset=big", "--dff=1234")
    assert vals[2] == "1234"
    assert vals[1] == "1024"  # the rest of the preset still lands


def test_preset_long4k_is_decoder_only_flash():
    vals = _materialize("--preset=long4k")
    assert vals[5] == "True" and vals[6] == "flash"
    assert vals[8] == "4096" and vals[9] == "4"


def test_ffn_activation_flag_list_matches_registry():
    """flags.py keeps a jax-import-free literal; pin it to the op registry."""
    from transformer_tpu.cli.flags import _FFN_ACTIVATION_NAMES
    from transformer_tpu.ops.ffn import FFN_ACTIVATIONS

    assert tuple(_FFN_ACTIVATION_NAMES) == FFN_ACTIVATIONS


_CONFIGS_SNIPPET = """
import dataclasses, json, sys
from absl import flags
from transformer_tpu.cli.flags import (
    define_flags, flags_to_model_config, flags_to_train_config,
)
define_flags()
flags.FLAGS(sys.argv)
print(json.dumps({
    "model": dataclasses.asdict(flags_to_model_config(100, 100)),
    "train": dataclasses.asdict(flags_to_train_config()),
}, default=str))
"""

# What each preset promises beyond its table row (cli/flags.py::_PRESETS is
# the one table): the fields a reader of BASELINE.json's configs expects.
_PRESET_PROMISES = {
    "tiny": {"num_layers": 2, "tie_embeddings": False, "label_smoothing": 0.0},
    "base": {"num_layers": 6, "d_model": 512, "decoder_only": False},
    "big": {"d_model": 1024, "num_heads": 16, "label_smoothing": 0.1},
    "tied": {"tie_embeddings": True, "tie_output": True},
    "long4k": {
        "decoder_only": True, "attention_impl": "flash",
        "sequence_length": 4096,
    },
}


@pytest.mark.parametrize("name", sorted(_PRESET_PROMISES))
def test_preset_builds_configs(name):
    """Each --preset folds into flags from which ModelConfig and TrainConfig
    build, and every value of its table row lands on the config field of
    the same name."""
    from transformer_tpu.cli.flags import _PRESETS

    assert set(_PRESETS) == set(_PRESET_PROMISES)
    built = json.loads(
        _run(_CONFIGS_SNIPPET, f"--preset={name}").splitlines()[-1]
    )
    for field, want in {**_PRESETS[name], **_PRESET_PROMISES[name]}.items():
        homes = [c for c in ("model", "train") if field in built[c]]
        assert homes, f"{name}: no config has a field {field!r}"
        for home in homes:
            assert built[home][field] == want, (name, home, field)


@pytest.mark.slow  # heavyweight: slow tier (fast tier keeps a specimen)
def test_serve_loop_end_to_end(tmp_path):
    """cli.serve: build a tiny export, pipe mixed raw/JSON/bad requests
    through the loop, get one JSONL response per request with the loop
    surviving the malformed one."""

    build = f"""
import jax
jax.config.update("jax_platforms", "cpu")
from transformer_tpu.config import ModelConfig
from transformer_tpu.models import transformer_init
from transformer_tpu.train.checkpoint import export_params
from transformer_tpu.data.tokenizer import SubwordTokenizer
tok = SubwordTokenizer.build_from_corpus(["ab cd ef gh"] * 3, target_vocab_size=270)
tok.save(r"{tmp_path}/vocab.subwords")
cfg = ModelConfig(num_layers=1, d_model=16, num_heads=2, dff=32,
                  input_vocab_size=tok.model_vocab_size,
                  target_vocab_size=tok.model_vocab_size,
                  max_position=32, dtype="float32", dropout_rate=0.0)
export_params(transformer_init(jax.random.PRNGKey(0), cfg), cfg, r"{tmp_path}/model")
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-c", build],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]

    requests = 'ab cd\n{"src": "ef gh", "beam": 2}\n{"nope": 1}\n'
    out = subprocess.run(
        [sys.executable, "-m", "transformer_tpu.cli.serve",
         "--platform=cpu",
         f"--export_path={tmp_path}/model",
         f"--src_vocab_file={tmp_path}/vocab.subwords",
         f"--tgt_vocab_file={tmp_path}/vocab.subwords",
         "--max_len=4"],
        input=requests, capture_output=True, text=True, timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
    assert len(lines) == 3, out.stdout
    assert "translation" in lines[0]
    assert "translation" in lines[1]
    assert "error" in lines[2]


@pytest.mark.slow  # heavyweight: slow tier (test_scheduler.py covers fast)
def test_serve_continuous_end_to_end(tmp_path):
    """cli.serve with a decoder-only export: the continuous-batching path
    (--serve_slots, the LM default) answers mixed prompt requests, a raw
    line, and a malformed line — one JSONL response per request, in order,
    identical to a --serve_slots=0 (grouped) run of the same requests."""
    import json

    build = f"""
import jax
jax.config.update("jax_platforms", "cpu")
from transformer_tpu.config import ModelConfig
from transformer_tpu.models import transformer_init
from transformer_tpu.train.checkpoint import export_params
from transformer_tpu.data.tokenizer import SubwordTokenizer
tok = SubwordTokenizer.build_from_corpus(["ab cd ef gh"] * 3, target_vocab_size=270)
tok.save(r"{tmp_path}/vocab.subwords")
cfg = ModelConfig(num_layers=1, d_model=16, num_heads=2, dff=32,
                  input_vocab_size=tok.model_vocab_size,
                  target_vocab_size=tok.model_vocab_size,
                  max_position=32, decoder_only=True, tie_output=True,
                  dtype="float32", dropout_rate=0.0)
export_params(transformer_init(jax.random.PRNGKey(0), cfg), cfg, r"{tmp_path}/model")
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-c", build],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]

    requests = (
        'ab cd\n'
        '{"prompt": "ef gh", "max_new": 3}\n'
        '{"prompt": "ab", "max_new": 8, "temperature": 0.8, "seed": 2}\n'
        '{broken\n'
    )

    def serve(extra):
        r = subprocess.run(
            [sys.executable, "-m", "transformer_tpu.cli.serve",
             "--platform=cpu",
             f"--export_path={tmp_path}/model",
             f"--tgt_vocab_file={tmp_path}/vocab.subwords",
             "--max_len=4", *extra],
            input=requests, capture_output=True, text=True, timeout=300,
            env=env,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        return [json.loads(l) for l in r.stdout.strip().splitlines()]

    cont = serve(["--serve_slots=2", "--prefill_chunk=4"])
    assert len(cont) == 4
    assert "continuation" in cont[0] and "continuation" in cont[1]
    assert "continuation" in cont[2] and "error" in cont[3]
    # Same answers as the grouped decode-to-completion path.
    grouped = serve(["--serve_slots=0"])
    assert [c.get("continuation") for c in cont[:3]] == [
        g.get("continuation") for g in grouped[:3]
    ]


def test_serve_lines_batches_one_decode_per_group(monkeypatch):
    """>=2 concurrent requests with the same decode signature must go
    through ONE translate() call (the batched-serving contract); different
    signatures split into their own groups; order is preserved and a
    malformed line is answered without a decode."""
    from transformer_tpu.cli import serve as serve_mod
    from transformer_tpu.config import ModelConfig
    from transformer_tpu.train import decode as decode_mod

    calls = []

    def fake_translate(params, cfg, src_tok, tgt_tok, sentences, **kw):
        calls.append((tuple(sentences), kw["beam_size"]))
        return [f"T({s})" for s in sentences]

    monkeypatch.setattr(decode_mod, "translate", fake_translate)
    cfg = ModelConfig(
        num_layers=1, d_model=16, num_heads=2, dff=32,
        input_vocab_size=32, target_vocab_size=32, max_position=16,
        decoder_only=False,
    )
    lines = [
        "hello there",                      # greedy group
        '{"src": "b", "beam": 2}',          # beam-2 group
        "not json but raw",                 # greedy group (same signature)
        "{broken json",                     # malformed: answered, no decode
        '{"src": "c", "beam": 2}',          # beam-2 group
    ]
    resp = serve_mod.serve_lines(lines, None, cfg, None, None)
    assert len(calls) == 2  # one decode per signature group
    grouped = {beam: s for s, beam in calls}
    assert grouped[1] == ("hello there", "not json but raw")
    assert grouped[2] == ("b", "c")
    assert resp[0] == {"translation": "T(hello there)"}
    assert resp[1] == {"translation": "T(b)"}
    assert resp[2] == {"translation": "T(not json but raw)"}
    assert "error" in resp[3]
    assert resp[4] == {"translation": "T(c)"}


def test_serve_lines_fill_mask(monkeypatch):
    """Encoder-only exports serve 'fill' requests: raw lines map to fill,
    same-top_k requests batch into ONE fill_mask() call, and kind
    mismatches answer with a routing error."""
    from transformer_tpu.cli import serve as serve_mod
    from transformer_tpu.config import ModelConfig
    from transformer_tpu.train import decode as decode_mod

    calls = []

    def fake_fill(params, cfg, tok, texts, top_k=5, **kw):
        calls.append((tuple(texts), top_k))
        return [
            {"filled": t.replace("[MASK]", "x"), "candidates": [[("x", 0.9)]]}
            for t in texts
        ]

    monkeypatch.setattr(decode_mod, "fill_mask", fake_fill)
    cfg = ModelConfig(
        num_layers=1, d_model=16, num_heads=2, dff=32,
        input_vocab_size=32, target_vocab_size=32, max_position=16,
        encoder_only=True,
    )
    resp = serve_mod.serve_lines(
        [
            "a [MASK] c",                    # raw line -> fill
            '{"fill": "d [MASK]", "top_k": 2}',
            '{"fill": "e [MASK]"}',          # default top_k group with [0]
            '{"src": "nope"}',               # wrong kind for this export
        ],
        None, cfg, None, None,
    )
    assert len(calls) == 2  # top_k=5 group (2 reqs) + top_k=2 group
    grouped = {k: t for t, k in calls}
    assert grouped[5] == ("a [MASK] c", "e [MASK]")
    assert grouped[2] == ("d [MASK]",)
    assert resp[0]["filled"] == "a x c"
    assert resp[0]["candidates"] == [[["x", 0.9]]]  # JSON-clean lists
    assert resp[1]["filled"] == "d x"
    assert resp[2]["filled"] == "e x"
    assert "serves 'fill'" in resp[3]["error"]

    # top_k out of range answers THAT request with the validation message.
    resp = serve_mod.serve_lines(
        ['{"fill": "a [MASK]", "top_k": 0}'], None, cfg, None, None
    )
    assert "top_k must be in" in resp[0]["error"]

    # A stray 'fill' key on a seq2seq export must not change routing
    # (unknown keys never did before the fill kind existed).
    seq_cfg = dataclasses.replace(cfg, encoder_only=False)

    def fake_translate(params, c, src_tok, tgt_tok, sentences, **kw):
        return [f"T({s})" for s in sentences]

    monkeypatch.setattr(decode_mod, "translate", fake_translate)
    resp = serve_mod.serve_lines(
        ['{"src": "hello", "fill": "stray"}'], None, seq_cfg, None, None
    )
    assert resp[0] == {"translation": "T(hello)"}


def test_serve_lines_sampled_requests_run_batch1(monkeypatch):
    """Greedy LM requests with one signature batch into ONE generate call;
    SAMPLED requests must each run alone — lm_generate holds one rng for a
    whole batch, so a co-batched sampled request's draws would depend on
    its neighbors (and diverge from the continuous scheduler's per-row
    picks)."""
    from transformer_tpu.cli import serve as serve_mod
    from transformer_tpu.config import ModelConfig
    from transformer_tpu.train import decode as decode_mod

    calls = []

    def fake_generate(params, cfg, tok, prompts, **kw):
        calls.append((tuple(prompts), kw.get("temperature"), kw.get("seed")))
        return [f"G({p})" for p in prompts]

    monkeypatch.setattr(decode_mod, "generate", fake_generate)
    cfg = ModelConfig(
        num_layers=1, d_model=16, num_heads=2, dff=32,
        input_vocab_size=32, target_vocab_size=32, max_position=16,
        decoder_only=True, tie_output=True,
    )
    resp = serve_mod.serve_lines(
        [
            '{"prompt": "a"}',                                # greedy group
            '{"prompt": "b", "temperature": 0.8, "seed": 2}', # alone
            '{"prompt": "c", "seed": 7}',  # greedy ignores seed: same group
            '{"prompt": "d", "temperature": 0.8, "seed": 2}', # alone
        ],
        None, cfg, None, None,
    )
    assert [r["continuation"] for r in resp] == [
        "G(a)", "G(b)", "G(c)", "G(d)"
    ]
    greedy = [c for c in calls if c[1] == 0.0]
    sampled = [c for c in calls if c[1] == 0.8]
    assert greedy == [(("a", "c"), 0.0, 0)]
    assert sorted(s[0] for s in sampled) == [("b",), ("d",)]


def test_serve_lines_error_isolation(monkeypatch):
    """A request with an unconvertible field answers with an error (not a
    crash), and a group-poisoning request must not fail its innocent
    co-batched neighbors: the group retries per member."""
    from transformer_tpu.cli import serve as serve_mod
    from transformer_tpu.config import ModelConfig
    from transformer_tpu.train import decode as decode_mod

    def fake_translate(params, cfg, src_tok, tgt_tok, sentences, **kw):
        if "poison" in sentences:
            raise RuntimeError("decode blew up")
        return [f"T({s})" for s in sentences]

    monkeypatch.setattr(decode_mod, "translate", fake_translate)
    cfg = ModelConfig(
        num_layers=1, d_model=16, num_heads=2, dff=32,
        input_vocab_size=32, target_vocab_size=32, max_position=16,
        decoder_only=False,
    )
    resp = serve_mod.serve_lines(
        [
            '{"src": "a", "beam": "four"}',  # unconvertible field
            "good one",
            "poison",                        # fails the batched decode
            "good two",
        ],
        None, cfg, None, None,
    )
    assert "error" in resp[0] and "ValueError" in resp[0]["error"]
    assert resp[1] == {"translation": "T(good one)"}
    assert "error" in resp[2] and "decode blew up" in resp[2]["error"]
    assert resp[3] == {"translation": "T(good two)"}


def test_distributed_cli_rejects_cpu_virtual_bf16(monkeypatch):
    """The known XLA:CPU abort (bf16 + single-process multi-virtual-device
    mesh) must be refused with a UsageError BEFORE any
    collective runs — a clear error + message, never a runtime abort. The
    predicate takes jax as a parameter, so pin it in-process with a stub
    (no XLA boot needed)."""
    from absl import app

    from transformer_tpu.cli import distributed_train as dt

    class StubJax:
        def __init__(self, backend="cpu", procs=1, ndev=4):
            self._b, self._p, self._n = backend, procs, ndev

        def default_backend(self):
            return self._b

        def process_count(self):
            return self._p

        def devices(self):
            return [object()] * self._n

    monkeypatch.delenv("TRANSFORMER_TPU_ALLOW_CPU_BF16", raising=False)
    with pytest.raises(app.UsageError, match="float32"):
        dt._reject_cpu_virtual_bf16(StubJax(), "bfloat16")

    # fp32 on the same mesh is the supported path and must pass the guard.
    dt._reject_cpu_virtual_bf16(StubJax(), "float32")

    # bf16 is fine wherever the abort can't happen: real TPU backend,
    # multi-host, or a single device.
    dt._reject_cpu_virtual_bf16(StubJax(backend="tpu"), "bfloat16")
    dt._reject_cpu_virtual_bf16(StubJax(procs=2), "bfloat16")
    dt._reject_cpu_virtual_bf16(StubJax(ndev=1), "bfloat16")

    # The escape hatch re-enables the combination for probing newer XLA.
    monkeypatch.setenv("TRANSFORMER_TPU_ALLOW_CPU_BF16", "1")
    dt._reject_cpu_virtual_bf16(StubJax(), "bfloat16")
