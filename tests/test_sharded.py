"""Sharded replicas (``serve/sharded.py``, ``--mesh``): one replica is one
multi-device pjit program.

The contract under test is BYTE parity: params replicate (every device runs
the identical float reduction — splitting reductions is what breaks bitwise
equality), the KV pool shards on its leading storage axis, and all
cross-shard traffic is GSPMD data movement. So a sharded scheduler at mesh
1, 2, or 4 must answer greedy AND seeded-sampled requests identically to
the historical single-device path — across cache variants, chunked prefill,
speculation, and prefix aliasing. Exercised on conftest's 8-virtual-CPU
platform, same as the distributed training tests.
"""

import jax
import numpy as np
import pytest

from transformer_tpu.config import ModelConfig
from transformer_tpu.data.tokenizer import SubwordTokenizer
from transformer_tpu.models import transformer_init
from transformer_tpu.serve import ContinuousScheduler, PrefixCache
from transformer_tpu.serve.sharded import (
    normalize_mesh_spec,
    parse_mesh_spec,
    serving_mesh,
)


def _cfg(tok, **kw) -> ModelConfig:
    base = dict(
        num_layers=1, d_model=16, num_heads=2, dff=32,
        input_vocab_size=tok.model_vocab_size,
        target_vocab_size=tok.model_vocab_size,
        max_position=64, decoder_only=True, tie_output=True,
        dtype="float32", dropout_rate=0.0,
    )
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def tok():
    return SubwordTokenizer.build_from_corpus(
        ["ab cd ef gh ij kl mn"] * 3, target_vocab_size=300
    )


# The acceptance matrix rides the same variants as the paged-pool tests:
# bf16 cache, int8 cache, GQA.
VARIANTS = {
    "bf16": dict(dtype="bfloat16"),
    "int8": dict(kv_cache_int8=True),
    "gqa": dict(num_kv_heads=1),
}

# Greedy AND seeded-sampled; wave 2 replays wave 1's prompt as a full
# prefix hit plus a divergent-tail partial hit (aliasing + CoW shard-wise).
WAVES = [
    [
        {"prompt": "ab cd ef gh ij", "max_new": 6},
        {"prompt": "ab cd ef gh kl", "max_new": 5, "temperature": 0.9,
         "seed": 3},
    ],
    [
        {"prompt": "ab cd ef gh ij", "max_new": 6},          # full hit
        {"prompt": "ab cd ef gh mn", "max_new": 4, "temperature": 0.7,
         "top_k": 4, "seed": 1},                             # partial hit
    ],
]


def _answers(params, cfg, tok, *, mesh=None, num_slots=2, **kw):
    s = ContinuousScheduler(
        params, cfg, tok, num_slots=num_slots, max_total=48,
        default_max_new=4, mesh=mesh, **kw,
    )
    out = []
    for wave in WAVES:
        out.extend(
            r.get("continuation") for r in s.run([dict(q) for q in wave])
        )
    return s, out


# --------------------------------------------------------------------------
# mesh-spec parsing


def test_parse_mesh_spec():
    assert parse_mesh_spec(None) is None
    assert parse_mesh_spec("") is None
    assert parse_mesh_spec(2) == 2
    assert parse_mesh_spec("4") == 4
    assert parse_mesh_spec("data=2") == 2
    # One canonical spelling: the replica's announced shape and the
    # supervisor's expectation must never alias into a false mismatch.
    assert normalize_mesh_spec("2") == normalize_mesh_spec("data=2") == "data=2"
    assert normalize_mesh_spec("") is None
    for bad in ("0", "-1", "model=2", "data=2,model=2", "x"):
        with pytest.raises(ValueError, match="mesh"):
            parse_mesh_spec(bad)


def test_serving_mesh_too_few_devices():
    with pytest.raises(ValueError, match="devices"):
        serving_mesh(len(jax.devices()) + 1)


# --------------------------------------------------------------------------
# byte parity: mesh 1/2/4 vs the unsharded path


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_mesh_parity_matrix(tok, variant):
    """Paged pool + prefix aliasing + chunked prefill + speculation, greedy
    and seeded-sampled requests: byte-identical answers at mesh 1, 2, 4 vs
    the unsharded scheduler (which also runs a different slot count, so
    parity is not an artifact of identical batching)."""
    cfg = _cfg(tok, **VARIANTS[variant])
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    common = dict(
        prefill_chunk=3, speculate_k=2, kv_layout="paged", kv_block=4,
    )
    _, want = _answers(
        params, cfg, tok, num_slots=2,
        prefix_cache=PrefixCache(cfg, block_tokens=4, budget_mb=8), **common,
    )
    for mesh in (1, 2, 4):
        s, got = _answers(
            params, cfg, tok, mesh=mesh, num_slots=4,
            prefix_cache=PrefixCache(cfg, block_tokens=4, budget_mb=8),
            **common,
        )
        assert got == want, f"mesh={mesh} diverged for {variant}"
        assert s.mesh_size == mesh and s._sharded is not None


def test_mesh_parity_dense(tok):
    """The dense layout shards on the slot axis; same parity contract."""
    cfg = _cfg(tok)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    common = dict(prefill_chunk=3, speculate_k=2)
    _, want = _answers(params, cfg, tok, num_slots=2, **common)
    for mesh in (2, 4):
        _, got = _answers(params, cfg, tok, mesh=mesh, num_slots=4, **common)
        assert got == want, f"mesh={mesh} diverged (dense)"


def test_sharded_layout_placement(tok):
    """The layout the docstring promises: params replicated, pool KV
    sharded on its leading storage axis, block table host-side as ever."""
    cfg = _cfg(tok)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    s = ContinuousScheduler(
        params, cfg, tok, num_slots=4, max_total=48, mesh=2,
        kv_layout="paged", kv_block=4,
    )
    p_leaf = jax.tree_util.tree_leaves(s.params)[0]
    assert p_leaf.sharding.is_fully_replicated
    for leaf in jax.tree_util.tree_leaves(s.pool.caches):
        spec = leaf.sharding.spec
        assert spec and spec[0], f"pool leaf not sharded on axis 0: {spec}"
        # Each of the 2 shards holds half the block rows.
        assert len(leaf.sharding.device_set) == 2
    # The paged pool was rounded up to a multiple of the mesh.
    assert jax.tree_util.tree_leaves(s.pool.caches)[0].shape[0] % 2 == 0


# --------------------------------------------------------------------------
# construction guards


def test_sharded_guards(tok):
    cfg = _cfg(tok)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="num_slots"):
        ContinuousScheduler(params, cfg, tok, num_slots=3, mesh=2)
    with pytest.raises(ValueError, match="paged_flash"):
        ContinuousScheduler(
            params, cfg, tok, num_slots=2, mesh=2,
            kv_layout="paged", decode_kernel="paged_flash",
        )


# --------------------------------------------------------------------------
# live-upgrade twin check grows sharding specs


def test_stage_params_refuses_mismatched_mesh(tok):
    """Staging weights committed to a DIFFERENT mesh answers a structured
    refusal (ValueError before anything is scheduled) and serving is
    untouched: no pending swap, and the next request still answers."""
    cfg = _cfg(tok)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    s = ContinuousScheduler(
        params, cfg, tok, num_slots=2, max_total=48, mesh=2,
        weight_version="v1",
    )
    want = [
        r.get("continuation")
        for r in s.run([{"prompt": "ab cd ef", "max_new": 4}])
    ]
    # Same structure/shapes/dtypes, but committed to a 4-device mesh:
    # the shape/dtype twin check passes, the sharding twin check must not.
    from jax.sharding import NamedSharding, PartitionSpec

    other = serving_mesh(4)
    wrong = jax.device_put(
        jax.tree.map(np.asarray, params),
        NamedSharding(other, PartitionSpec()),
    )
    with pytest.raises(ValueError, match="sharding"):
        s.stage_params(wrong, "v2")
    assert not s.swap_pending
    got = [
        r.get("continuation")
        for r in s.run([{"prompt": "ab cd ef", "max_new": 4}])
    ]
    assert got == want  # zero serving impact


def test_stage_params_host_arrays_swap_cleanly(tok):
    """The checkpoint-load path: host (numpy) arrays carry no committed
    sharding, so they pass the twin check, get placed onto the serving
    mesh, and the swap changes answers with zero recompiles of the
    sharded twins."""
    cfg = _cfg(tok)
    p1 = transformer_init(jax.random.PRNGKey(0), cfg)
    p2 = jax.tree.map(np.asarray, transformer_init(jax.random.PRNGKey(1), cfg))
    s = ContinuousScheduler(
        params := p1, cfg, tok, num_slots=2, max_total=48, mesh=2,
        weight_version="v1",
    )
    del params
    req = {"prompt": "ab cd ef", "max_new": 4}
    s.run([dict(req)])
    before = s._sharded.pool_step._cache_size()
    s.stage_params(p2, "v2")
    assert s.swap_pending
    out = s.run([dict(req)])  # drain triggers the flip at a step boundary
    assert s.weight_version == "v2" and not s.swap_pending
    assert out[0].get("weight_version") == "v2"
    leaf = jax.tree_util.tree_leaves(s.params)[0]
    assert leaf.sharding.is_fully_replicated  # placed onto the serving mesh
    assert s._sharded.pool_step._cache_size() == before  # zero recompiles


# --------------------------------------------------------------------------
# the same parity through the wire: a real replica process behind a Router


@pytest.mark.parametrize("mesh", [2])
def test_mesh_parity_through_router(tmp_path, mesh):
    """A replica worker started with ``--mesh N`` (it grows its own virtual
    CPU platform before importing jax) announces ``data=N`` on its ready
    line and answers greedy AND seeded-sampled requests through a Router
    byte-identically to the unsharded scheduler in this process."""
    import json

    from transformer_tpu.serve.replica import build_model_from_spec
    from transformer_tpu.serve.router import ReplicaProcess, Router

    spec = {
        "config": {
            "num_layers": 1, "d_model": 16, "num_heads": 2, "dff": 32,
            "max_position": 32, "decoder_only": True, "tie_output": True,
            "dtype": "float32", "dropout_rate": 0.0,
        },
        "seed": 0,
        "corpus": ["ab cd ef gh ij kl mn"] * 3,
        "target_vocab_size": 300,
    }
    spec_file = tmp_path / "spec.json"
    spec_file.write_text(json.dumps(spec))
    params, cfg, spec_tok = build_model_from_spec(spec)
    reqs = [q for wave in WAVES for q in wave]
    want = [
        r.get("continuation")
        for r in ContinuousScheduler(params, cfg, spec_tok, num_slots=2).run(
            [dict(q) for q in reqs]
        )
    ]
    assert all(c is not None for c in want)
    link = ReplicaProcess.spawn(0, [
        "--model_spec", str(spec_file), "--serve_slots", "4",
        "--heartbeat_ms", "100", "--mesh", str(mesh),
    ])
    router = Router(
        [link], encode=spec_tok.encode, bos_id=spec_tok.bos_id,
        heartbeat_timeout_s=30.0,
    )
    link.start_reader(router.inbox)
    try:
        got = [o.get("continuation") for o in router.run([dict(q) for q in reqs])]
        reported = link.mesh
    finally:
        router.shutdown()
    assert got == want, f"mesh={mesh} replica diverged from the unsharded path"
    assert reported == f"data={mesh}"
