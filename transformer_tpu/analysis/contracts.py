"""Abstract shape/dtype contract checking — no device execution.

Every check traces public entry points with ``jax.eval_shape`` or
``jax.make_jaxpr`` over abstract ``ShapeDtypeStruct`` inputs (even the
parameter pytree is abstract: ``transformer_init`` is itself eval_shape'd),
so the whole suite is CPU-safe, allocation-free, and fast enough for tier-1.
This is the Mesh-TensorFlow lesson (PAPERS.md) applied to this repo: the
invariants the code PROMISES in its docstrings become machine-checked
contracts that fail at trace time, rounds before a TPU would have noticed.

Contracts:

- **cache_parity** — prefill and incremental decode must produce caches
  with identical pytree structure, shapes, AND dtypes for every cache
  variant (plain bf16, int8+scales, rolling window, GQA). A drift here is
  the classic silent serving bug: the slot pool admits via prefill but
  steps incrementally, so a mismatch poisons every request after the first.
- **verify_cache_parity** — a speculative verify forward (one S_q = k+1
  call through ``transformer_verify``) must leave caches structurally
  indistinguishable from k+1 repeated incremental steps, and return
  per-position logits — the speculative scheduler interleaves the two
  paths (plus index rollback) over one slot pool.
- **prefix_restore_parity** — a slot cache rebuilt from prefix-cache KV
  blocks (``ops.attention.slice_kv_blocks`` → ``insert_kv_blocks``) must
  equal a chunk-prefilled cache in structure, shape, and dtype across
  plain/int8/GQA layouts: cache-hit admissions prefill the unmatched
  suffix INTO the restored cache, so restore/prefill drift poisons every
  hit.
- **softmax_f32** — ``dot_product_attention`` promises its softmax runs in
  fp32 even under bf16 compute (``ops/attention.py``); checked by walking
  the jaxpr of the forward for ``exp`` equations and asserting their
  operands are f32.
- **residual_dtype** — the residual stream must stay in
  ``cfg.compute_dtype`` end to end (no silent bf16→f32 promotion that would
  double HBM traffic and MXU pressure).
- **mask_broadcast** — padding/causal/cache-prefix masks must broadcast
  against (B, H, S_q, S_k) attention logits.
- **decode_shapes** — greedy/beam/LM decode return (B, max_len)/(B,
  max_new) int32 ids.
- **train_step_dtypes** — one abstract optimizer step preserves every
  parameter's dtype (param_dtype, not compute dtype) and advances ``step``.
- **telemetry_inert** — the obs instrumentation wrappers
  (``obs.telemetry.timed_call`` composed with ``obs.trace.traced_call`` —
  exactly what the Trainer installs around its jitted step dispatches when
  telemetry/tracing are on) must produce a jaxpr BYTE-IDENTICAL to the
  uninstrumented twin's for the train step AND the serving pool step, slot
  prefill, and speculative verify programs (tracing-on vs. tracing-off;
  the scheduler's own span recording is inline host code at step
  boundaries): telemetry records host-side scalars and can never leak an
  operation into traced code.
- **fault_plane_inert** — an ARMED fault plane (``serve.resilience``)
  must leave the serving hot paths' jaxprs byte-identical to the
  disarmed twin's: injection points live in host code between dispatches
  (admission, drafter calls, sink writes), never inside a trace. Any
  future "optimization" that threads a fault flag into a jitted function
  — minting a recompile per breaker flip, the exact bug the
  ``resilience_retrace_report`` budget guards at runtime — fails here
  abstractly first. The check also proves the plane is LIVE while armed
  (a fired point raises), so the identity is not vacuous.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr
import numpy as np

from transformer_tpu.analysis.configs import TINY_TRAIN, matrix
from transformer_tpu.config import ModelConfig

_KEY = jax.ShapeDtypeStruct((2,), np.uint32)  # abstract PRNGKey


@dataclasses.dataclass(frozen=True)
class ContractResult:
    contract: str
    config: str
    ok: bool
    detail: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        mark = "PASS" if self.ok else "FAIL"
        return f"{mark} {self.contract}[{self.config}] {self.detail}"


def _ids(batch: int, length: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((batch, length), np.int32)


def abstract_params(cfg: ModelConfig):
    """The parameter pytree as ShapeDtypeStructs — nothing is allocated."""
    from transformer_tpu.models.transformer import transformer_init

    return jax.eval_shape(lambda k: transformer_init(k, cfg), _KEY)


def _tree_spec(tree) -> list[tuple[str, tuple, str]]:
    """Canonical (path, shape, dtype) list for structure+layout comparison."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return [
        (jax.tree_util.keystr(path), tuple(leaf.shape), str(leaf.dtype))
        for path, leaf in flat
    ]


# --------------------------------------------------------------------------
# individual contracts (each returns a detail string or raises AssertionError)


def check_cache_parity(cfg: ModelConfig, batch: int = 2, n: int = 4) -> str:
    """Prefill-built caches and step-built caches must be indistinguishable
    in structure, shape, and dtype (the serving scheduler mixes the two
    paths over one slot pool)."""
    from transformer_tpu.models.decoder import (
        init_decoder_caches,
        precompute_cross_kvs,
    )
    from transformer_tpu.models.encoder import encoder_apply
    from transformer_tpu.models.transformer import (
        transformer_decode_step,
        transformer_prefill,
    )
    from transformer_tpu.ops.masks import make_padding_mask

    params = abstract_params(cfg)
    total = 16

    def encoder_state(params, tokens):
        # Seq2seq decode attends a (static) encoder output through
        # precomputed cross K/Vs — the same wiring greedy_decode uses.
        if cfg.decoder_only:
            return None, None, None
        enc_mask = make_padding_mask(tokens)
        enc_out, _ = encoder_apply(params["encoder"], tokens, enc_mask, cfg)
        return enc_out, enc_mask, precompute_cross_kvs(
            params["decoder"], enc_out, cfg
        )

    def prefill_path(params, tokens):
        enc_out, enc_mask, cross_kvs = encoder_state(params, tokens)
        caches = init_decoder_caches(cfg, batch, total)
        _, caches = transformer_prefill(
            params, tokens, enc_out, enc_mask, caches, 0, cfg,
            cross_kvs=cross_kvs,
        )
        return caches

    def step_path(params, tokens):
        enc_out, enc_mask, cross_kvs = encoder_state(params, tokens)
        caches = init_decoder_caches(cfg, batch, total)
        for i in range(n):
            _, caches = transformer_decode_step(
                params, tokens[:, i : i + 1], enc_out, enc_mask, caches, i,
                cfg, cross_kvs=cross_kvs,
            )
        return caches

    tokens = _ids(batch, n)
    via_prefill = jax.eval_shape(prefill_path, params, tokens)
    via_steps = jax.eval_shape(step_path, params, tokens)
    a, b = _tree_spec(via_prefill), _tree_spec(via_steps)
    assert a == b, (
        "prefill and incremental step disagree on cache layout/dtype:\n"
        f"  prefill: {a}\n  steps:   {b}"
    )
    # The variant-specific storage promises, stated explicitly:
    leaf = {path: (shape, dtype) for path, shape, dtype in a}
    k_path = next(p for p in leaf if p.endswith("['k']"))
    if cfg.kv_cache_int8:
        assert leaf[k_path][1] == "int8", f"int8 cache stores k as {leaf[k_path][1]}"
        scale_path = next(p for p in leaf if p.endswith("['k_scale']"))
        assert leaf[scale_path][1] == "float32", "int8 scales must be fp32"
    else:
        assert leaf[k_path][1] == str(cfg.compute_dtype), (
            f"cache k dtype {leaf[k_path][1]} != compute dtype {cfg.compute_dtype}"
        )
    buf_len = leaf[k_path][0][1]
    if cfg.attention_window:
        expected = min(cfg.attention_window, total)
        assert buf_len == expected, (
            f"rolling cache buffer is {buf_len} slots, want {expected}"
        )
    else:
        assert buf_len == total, f"cache buffer {buf_len} != max_len {total}"
    kv_heads = leaf[k_path][0][2]
    assert kv_heads == cfg.kv_heads, (
        f"cache carries {kv_heads} kv heads, config says {cfg.kv_heads}"
    )
    return f"{len(a)} cache leaves identical across prefill/step"


def check_verify_cache_parity(cfg: ModelConfig, batch: int = 2, k: int = 3) -> str:
    """One speculative verify forward (S_q = k + 1 through
    ``transformer_verify``) and ``k + 1`` repeated incremental steps must
    leave caches with identical pytree structure, shapes, AND dtypes — the
    speculative scheduler interleaves verify forwards, single-token steps,
    and index rollback over ONE slot pool, so any layout drift between the
    paths poisons every request that follows a mixed step. Verify must
    also return per-position logits (B, k + 1, V) whose dtype matches the
    step path's — the acceptance rule compares them position by position."""
    from transformer_tpu.models.decoder import init_decoder_caches
    from transformer_tpu.models.transformer import (
        transformer_decode_step,
        transformer_verify,
    )

    total = 16
    params = abstract_params(cfg)

    def verify_path(params, tokens):
        caches = init_decoder_caches(cfg, batch, total)
        return transformer_verify(params, tokens, caches, 0, cfg)

    def step_path(params, tokens):
        caches = init_decoder_caches(cfg, batch, total)
        logits = None
        for i in range(k + 1):
            logits, caches = transformer_decode_step(
                params, tokens[:, i : i + 1], None, None, caches, i, cfg
            )
        return logits, caches

    tokens = _ids(batch, k + 1)
    v_logits, via_verify = jax.eval_shape(verify_path, params, tokens)
    s_logits, via_steps = jax.eval_shape(step_path, params, tokens)
    a, b = _tree_spec(via_verify), _tree_spec(via_steps)
    assert a == b, (
        "speculative verify and repeated incremental steps disagree on "
        f"cache layout/dtype:\n  verify: {a}\n  steps:  {b}"
    )
    want = (batch, k + 1, cfg.target_vocab_size)
    assert v_logits.shape == want, (
        f"verify logits are {v_logits.shape}, want per-position {want}"
    )
    assert v_logits.dtype == s_logits.dtype, (
        f"verify logits dtype {v_logits.dtype} != step logits dtype "
        f"{s_logits.dtype} — the acceptance comparison would mix dtypes"
    )
    return (
        f"{len(a)} cache leaves identical across verify/{k + 1} steps; "
        f"logits {want} {v_logits.dtype}"
    )


def check_prefix_restore_parity(
    cfg: ModelConfig, batch: int = 1, blocks: int = 2, block: int = 4
) -> str:
    """A slot cache rebuilt from prefix-cache blocks (``slice_kv_blocks`` →
    ``insert_kv_blocks`` round trip, index advanced to the restored width)
    must be structurally indistinguishable — pytree structure, shapes, AND
    dtypes — from one chunk-prefilled over the same tokens: the scheduler
    prefills the unmatched SUFFIX into the restored cache and then decodes
    incrementally, so any layout drift between restore and prefill poisons
    every cache-hit request. Traced abstractly (eval_shape) across
    plain/int8/GQA layouts; rolling-window configs are excluded (the prefix
    cache refuses them at construction)."""
    from transformer_tpu.models.decoder import init_decoder_caches
    from transformer_tpu.models.transformer import transformer_prefill
    from transformer_tpu.ops.attention import insert_kv_blocks, slice_kv_blocks

    total = 16
    n = blocks * block
    params = abstract_params(cfg)

    def prefill_path(params, tokens):
        caches = init_decoder_caches(cfg, batch, total)
        _, caches = transformer_prefill(
            params, tokens, None, None, caches, 0, cfg, chunk=block
        )
        return caches

    def restore_path(params, tokens):
        donor = prefill_path(params, tokens)
        fresh = init_decoder_caches(cfg, batch, total)
        out = []
        for d, c in zip(donor, fresh):
            for j in range(blocks):
                c = insert_kv_blocks(
                    c, slice_kv_blocks(d, j * block, block), j * block
                )
            out.append(dict(c, index=jnp.asarray(n, jnp.int32)))
        return out

    tokens = _ids(batch, n)
    a = _tree_spec(jax.eval_shape(prefill_path, params, tokens))
    b = _tree_spec(jax.eval_shape(restore_path, params, tokens))
    assert a == b, (
        "trie-restored and chunk-prefilled caches disagree on "
        f"layout/dtype:\n  prefill: {a}\n  restore: {b}"
    )
    return (
        f"{len(a)} cache leaves identical across restore/prefill "
        f"({blocks}x{block}-token blocks)"
    )


def check_paged_alias_parity(
    cfg: ModelConfig, num_slots: int = 2, max_total: int = 16, block: int = 4
) -> str:
    """Paged-KV structural parity (the aliased-restore sibling of
    ``prefix_restore_parity``): (1) the per-slot views the paged step
    gathers through the block tables must be pytree/shape/dtype identical
    to the DENSE slot pool the model forward was written against — the
    precondition of byte-identical answers across ``--kv_layout``; (2) a
    restore through the pool — the host-block scatter write (an ALIASED
    device-tier hit is a pure table op and cannot perturb the pool by
    construction) — must leave the pool structurally indistinguishable
    from a chunked prefill over the same tokens, across plain/int8/GQA
    layouts (rolling windows are refused by the paged pool)."""
    import numpy as np

    from transformer_tpu.serve.scheduler import (
        _paged_views,
        _pool_write_blocks,
        _slot_prefill_paged,
        abstract_paged_pool,
        abstract_pool_caches,
    )

    pool_blocks = 1 + num_slots * (-(-max_total // block))
    pool, table, index = abstract_paged_pool(
        cfg, num_slots, max_total, pool_blocks, block
    )
    dense = abstract_pool_caches(cfg, num_slots, max_total)
    views = jax.eval_shape(
        lambda p, t, i: _paged_views(p, t, i, max_total, cfg.head_dim),
        pool, table, index,
    )
    a, b = _tree_spec(views), _tree_spec(dense)
    assert a == b, (
        "gathered paged views diverge from the dense slot pool:\n"
        f"  dense: {b}\n  paged: {a}"
    )

    params = abstract_params(cfg)
    n_blocks, n = 2, 2 * block
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731
    after_prefill = jax.eval_shape(
        lambda p, c, tb, s, pr, st: _slot_prefill_paged(
            p, c, tb, s, pr, st, cfg, block, block, max_total
        )[1],
        params, pool, table, i32(), _ids(1, n), i32(),
    )
    host_blocks = [
        {
            key: jax.ShapeDtypeStruct(
                (n_blocks, block) + leaf.shape[2:], leaf.dtype
            )
            for key, leaf in layer.items()
        }
        for layer in pool
    ]
    after_restore = jax.eval_shape(
        _pool_write_blocks, pool, i32(n_blocks), host_blocks
    )
    p_spec = _tree_spec(after_prefill)
    r_spec = _tree_spec(after_restore)
    assert p_spec == r_spec == _tree_spec(list(pool)), (
        "restore and chunked prefill disagree on the pool layout:\n"
        f"  prefill: {p_spec}\n  restore: {r_spec}"
    )
    return (
        f"{len(a)} view leaves dense-identical; pool layout stable across "
        f"restore/prefill ({n_blocks}x{block}-token blocks)"
    )


def _walk_eqns(jaxpr) -> Iterable:
    """Every equation, recursing through pjit/scan/while/cond sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _as_jaxprs(v):
                yield from _walk_eqns(sub)


def _as_jaxprs(v) -> Iterable:
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _as_jaxprs(item)


def check_softmax_f32(cfg: ModelConfig, batch: int = 2, length: int = 8) -> str:
    """Every ``exp`` in the forward jaxpr (softmax is the only exp in a
    relu/bf16 config) must consume f32 — the documented f32-softmax
    contract of ``dot_product_attention``."""
    from transformer_tpu.models.transformer import transformer_apply

    params = abstract_params(cfg)
    inp = None if (cfg.decoder_only or cfg.encoder_only) else _ids(batch, length)
    jaxpr = jax.make_jaxpr(
        lambda p, i, t: transformer_apply(p, i, t, cfg)
    )(params, inp, _ids(batch, length))
    exps = [e for e in _walk_eqns(jaxpr.jaxpr) if e.primitive.name == "exp"]
    assert exps, "no exp equation found — did softmax disappear from the forward?"
    bad = [
        str(e.invars[0].aval.dtype)
        for e in exps
        if e.invars[0].aval.dtype != jnp.float32
    ]
    assert not bad, (
        f"{len(bad)}/{len(exps)} exp ops run outside f32 ({sorted(set(bad))}) "
        f"under compute dtype {cfg.dtype} — the f32-softmax contract is broken"
    )
    return f"all {len(exps)} exp ops in f32"


def check_residual_dtype(cfg: ModelConfig, batch: int = 2, length: int = 8) -> str:
    """The pre-projection residual stream stays in the compute dtype — a
    silent promotion to f32 would double decode HBM traffic."""
    from transformer_tpu.models.transformer import transformer_hidden_apply

    params = abstract_params(cfg)
    inp = None if (cfg.decoder_only or cfg.encoder_only) else _ids(batch, length)
    hidden, _ = jax.eval_shape(
        lambda p, i, t: transformer_hidden_apply(p, i, t, cfg),
        params, inp, _ids(batch, length),
    )
    assert hidden.dtype == cfg.compute_dtype, (
        f"residual stream is {hidden.dtype}, compute dtype is "
        f"{cfg.compute_dtype} — silent promotion"
    )
    assert hidden.shape == (batch, length, cfg.d_model)
    return f"hidden (B,S,{cfg.d_model}) stays {hidden.dtype}"


def check_mask_broadcast(cfg: ModelConfig, batch: int = 2, length: int = 8) -> str:
    """All mask builders must broadcast against (B, H, S_q, S_k) logits."""
    from transformer_tpu.ops.masks import (
        make_cache_prefix_mask,
        make_causal_mask,
        make_padding_mask,
    )

    logits_shape = (batch, cfg.num_heads, length, length)

    def build(ids):
        return (
            make_padding_mask(ids),
            make_causal_mask(length, window=cfg.attention_window),
            make_cache_prefix_mask(jnp.int32(0), length, length),
        )

    pad, causal, prefix = jax.eval_shape(build, _ids(batch, length))
    for name, m in (("padding", pad), ("causal", causal), ("prefix", prefix)):
        assert m.dtype == jnp.bool_, f"{name} mask dtype {m.dtype} != bool"
        try:
            np.broadcast_shapes(m.shape, logits_shape)
        except ValueError as e:
            raise AssertionError(
                f"{name} mask {m.shape} does not broadcast to logits "
                f"{logits_shape}: {e}"
            ) from None
    return f"padding/causal/prefix masks broadcast to {logits_shape}"


def check_decode_shapes(cfg: ModelConfig, batch: int = 2) -> str:
    """Decode entry points return (B, max_len)/(B, max_new) int32 ids."""
    params = abstract_params(cfg)
    max_len = 6
    if cfg.decoder_only:
        from transformer_tpu.train.decode import lm_generate

        out = jax.eval_shape(
            lambda p, ids: lm_generate.__wrapped__(
                p, ids, cfg, max_len, eos_id=2, prefill_len=4
            ),
            params, _ids(batch, 5),
        )
        assert out.shape == (batch, max_len) and out.dtype == jnp.int32, (
            f"lm_generate -> {out.shape} {out.dtype}, want ({batch}, {max_len}) int32"
        )
        return f"lm_generate -> ({batch}, {max_len}) int32"
    from transformer_tpu.train.decode import beam_search_decode, greedy_decode

    greedy = jax.eval_shape(
        lambda p, src: greedy_decode.__wrapped__(p, src, cfg, max_len, 1, 2),
        params, _ids(batch, 5),
    )
    beam = jax.eval_shape(
        lambda p, src: beam_search_decode.__wrapped__(
            p, src, cfg, max_len, 1, 2, beam_size=2
        ),
        params, _ids(batch, 5),
    )
    for name, out in (("greedy_decode", greedy), ("beam_search_decode", beam)):
        assert out.shape == (batch, max_len) and out.dtype == jnp.int32, (
            f"{name} -> {out.shape} {out.dtype}, want ({batch}, {max_len}) int32"
        )
    return f"greedy+beam -> ({batch}, {max_len}) int32"


def check_train_step_dtypes(cfg: ModelConfig) -> str:
    """One abstract optimizer step: parameter dtypes preserved exactly
    (param_dtype — the optimizer must not let compute-dtype activations
    bleed into the master weights), metrics scalar f32, step advanced."""
    from transformer_tpu.train.state import TrainState, make_optimizer
    from transformer_tpu.train.trainer import make_train_step

    train_cfg = TINY_TRAIN
    if cfg.encoder_only:
        train_cfg = dataclasses.replace(train_cfg, objective="mlm")
    step_fn = make_train_step(cfg, train_cfg)
    params = abstract_params(cfg)

    def init_and_step(params, src, tgt, rng):
        tx = make_optimizer(cfg, train_cfg)
        state = TrainState(
            step=jnp.int32(0), params=params, opt_state=tx.init(params)
        )
        return step_fn(state, src, tgt, rng)

    B, L = train_cfg.batch_size, train_cfg.sequence_length
    new_state, metrics = jax.eval_shape(
        init_and_step, params, _ids(B, L), _ids(B, L), _KEY
    )
    before = _tree_spec(params)
    after = _tree_spec(new_state.params)
    assert before == after, (
        "optimizer step changed parameter shapes/dtypes:\n"
        f"  before: {before}\n  after:  {after}"
    )
    assert new_state.step.dtype == jnp.int32
    loss = metrics["loss"]
    assert loss.shape == () and loss.dtype == jnp.float32, (
        f"loss metric is {loss.shape} {loss.dtype}, want scalar f32"
    )
    return f"{len(after)} param leaves dtype-stable through the optimizer step"


def check_telemetry_inert(cfg: ModelConfig) -> str:
    """Instrumented and uninstrumented step functions must trace to
    byte-identical jaxprs. The instrumented twin is built with the real
    wrappers the telemetry-enabled Trainer installs around its step
    dispatches — ``obs.telemetry.timed_call`` feeding a live registry
    histogram + counter, COMPOSED with ``obs.trace.traced_call``
    opening a real span on a live tracer (the
    ``--trace`` stack, spans emitted through a live FlightRecorder tap
    into a real in-memory EventLog); the serving pool step, slot prefill,
    and speculative verify programs are traced through the same wrappers.
    Any
    future 'improvement' that lets a recorded value flow back into the
    computation — or adds so much as a ``convert_element_type`` to the
    trace — fails here, rounds before a byte-identity serving test would
    catch it on hardware. (The scheduler's own span recording is inline
    host code at step boundaries; its inertness is pinned by the
    byte-identity + zero-recompile tests in tests/test_obs.py and
    tests/test_trace.py.)"""
    import io

    from transformer_tpu.obs import MetricsRegistry
    from transformer_tpu.obs.events import EventLog
    from transformer_tpu.obs.flight import FlightRecorder
    from transformer_tpu.obs.telemetry import timed_call
    from transformer_tpu.obs.trace import Tracer, traced_call
    from transformer_tpu.train.state import TrainState, make_optimizer
    from transformer_tpu.train.trainer import make_train_step

    import re

    reg = MetricsRegistry()
    span_sink = io.StringIO()
    # The flight recorder armed exactly as production arms it: it taps
    # the tracer's emit path (every span rides the ring).
    flight = FlightRecorder(None, capacity=64)
    tracer = Tracer(flight.tap(EventLog(span_sink).emit))

    def canon(jaxpr) -> str:
        # custom_jvp equations print closure thunks with their memory
        # address (`jvp_jaxpr_thunk=<function ... at 0x...>`); two traces of
        # IDENTICAL programs differ there. Mask addresses, compare the rest
        # byte-for-byte.
        return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))

    def twins(fn):
        # The exact production composition: traced_call around timed_call
        # (trainer._wrap_steps_for_dispatch_timing order).
        wrapped = timed_call(
            fn, reg.histogram("contract_seconds"), reg.counter("contract_total")
        )
        wrapped = traced_call(wrapped, tracer, "contract.step")
        return fn, wrapped

    checked = []

    # -- train step ---------------------------------------------------------
    train_cfg = TINY_TRAIN
    if cfg.encoder_only:
        train_cfg = dataclasses.replace(train_cfg, objective="mlm")
    step_fn = make_train_step(cfg, train_cfg)
    params = abstract_params(cfg)

    def driver(step):
        def init_and_step(params, src, tgt, rng):
            tx = make_optimizer(cfg, train_cfg)
            state = TrainState(
                step=jnp.int32(0), params=params, opt_state=tx.init(params)
            )
            return step(state, src, tgt, rng)

        return init_and_step

    B, L = train_cfg.batch_size, train_cfg.sequence_length
    plain, wrapped = twins(step_fn)
    a = canon(jax.make_jaxpr(driver(plain))(params, _ids(B, L), _ids(B, L), _KEY))
    b = canon(jax.make_jaxpr(driver(wrapped))(params, _ids(B, L), _ids(B, L), _KEY))
    assert a == b, "timed_call changed the TRAIN step jaxpr — telemetry leaked into traced code"
    checked.append("train_step")

    # -- serving pool step / prefill / verify (decoder-only exports) --------
    if cfg.decoder_only:
        from transformer_tpu.serve.scheduler import (
            _pool_step,
            _pool_verify,
            _slot_prefill,
            abstract_pool_caches,
        )

        slots, total = 2, 16
        pool = abstract_pool_caches(cfg, slots, total)
        toks = jax.ShapeDtypeStruct((slots,), np.int32)
        step_raw = _pool_step.__wrapped__
        plain, wrapped = twins(lambda p, c, t: step_raw(p, c, t, cfg))
        a = canon(jax.make_jaxpr(plain)(params, pool, toks))
        b = canon(jax.make_jaxpr(wrapped)(params, pool, toks))
        assert a == b, (
            "telemetry wrappers changed the POOL step jaxpr — telemetry "
            "leaked into traced serving code"
        )
        checked.append("pool_step")
        prefill_raw = _slot_prefill.__wrapped__
        prompt = jax.ShapeDtypeStruct((1, 8), np.int32)
        scalar = jax.ShapeDtypeStruct((), np.int32)
        plain, wrapped = twins(
            lambda p, c, s, pr, st: prefill_raw(p, c, s, pr, st, cfg, 0)
        )
        a = canon(jax.make_jaxpr(plain)(params, pool, scalar, prompt, scalar))
        b = canon(jax.make_jaxpr(wrapped)(params, pool, scalar, prompt, scalar))
        assert a == b, (
            "telemetry wrappers changed the SLOT prefill jaxpr — telemetry "
            "leaked into traced serving code"
        )
        checked.append("slot_prefill")
        if not cfg.attention_window:
            # Verify rides the same S_q>1 cache-write path rollback needs;
            # rolling-window configs refuse speculation, so the program
            # does not exist for them.
            verify_raw = _pool_verify.__wrapped__
            rows = jax.ShapeDtypeStruct((slots, 3), np.int32)
            plain, wrapped = twins(lambda p, c, t: verify_raw(p, c, t, cfg))
            a = canon(jax.make_jaxpr(plain)(params, pool, rows))
            b = canon(jax.make_jaxpr(wrapped)(params, pool, rows))
            assert a == b, (
                "telemetry wrappers changed the VERIFY jaxpr — telemetry "
                "leaked into traced serving code"
            )
            checked.append("pool_verify")
    assert reg.histogram("contract_seconds").hist.count >= len(checked), (
        "the instrumented twin never recorded — the contract exercised a "
        "dead wrapper"
    )
    assert tracer.stats["ended"] >= len(checked) and tracer.open_count == 0, (
        "the traced twin never opened/closed a span — the tracing side of "
        "the contract is vacuous"
    )
    assert "trace.span" in span_sink.getvalue(), (
        "the tracer's spans never reached the event log"
    )
    assert flight.depth() > 0 and flight.dump("request")["spans"], (
        "the tracer's spans never rode the flight-recorder ring"
    )
    return (
        "jaxpr-identical twins (timed+traced, flight armed): "
        f"{', '.join(checked)}"
    )


def check_fault_plane_inert(cfg: ModelConfig) -> str:
    """Armed-vs-disarmed fault-plane twins of the serving hot paths must
    trace to byte-identical jaxprs (see module docstring): the plane is
    host-side by construction, and this contract keeps it that way."""
    import re

    from transformer_tpu.serve import resilience
    from transformer_tpu.serve.scheduler import (
        _pool_step,
        _slot_prefill,
        abstract_pool_caches,
    )

    def canon(jaxpr) -> str:
        return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))

    params = abstract_params(cfg)
    slots, total = 2, 16
    pool = abstract_pool_caches(cfg, slots, total)
    toks = jax.ShapeDtypeStruct((slots,), np.int32)
    prompt = jax.ShapeDtypeStruct((1, 8), np.int32)
    slot = jax.ShapeDtypeStruct((), np.int32)
    start = jax.ShapeDtypeStruct((), np.int32)
    step_raw = _pool_step.__wrapped__
    prefill_raw = _slot_prefill.__wrapped__

    def trace_all():
        a = canon(jax.make_jaxpr(
            lambda p, c, t: step_raw(p, c, t, cfg))(params, pool, toks))
        b = canon(jax.make_jaxpr(
            lambda p, c, s, pr, st: prefill_raw(p, c, s, pr, st, cfg, 0)
        )(params, pool, slot, prompt, start))
        return a, b

    plane = resilience.FaultPlane.parse("serve.prefill:p=1")
    disarmed = trace_all()
    with resilience.active(plane):
        armed = trace_all()
        # Non-vacuous: the armed plane really fires at its host-side site.
        fired = False
        try:
            resilience.maybe_fail("serve.prefill")
        except resilience.InjectedFault:
            fired = True
        assert fired, "armed fault plane never fired — the contract is vacuous"
    assert disarmed[0] == armed[0], (
        "an armed fault plane changed the POOL step jaxpr — injection "
        "leaked into traced serving code"
    )
    assert disarmed[1] == armed[1], (
        "an armed fault plane changed the SLOT prefill jaxpr — injection "
        "leaked into traced serving code"
    )
    return "jaxpr-identical armed/disarmed twins: pool_step, slot_prefill"


# --------------------------------------------------------------------------
# driver

_CONTRACTS: list[tuple[str, Callable[[ModelConfig], str], Callable[[ModelConfig], bool]]] = [
    ("cache_parity", check_cache_parity, lambda c: not c.encoder_only),
    # Speculation serves the LM path only; the structural parity still
    # covers every cache variant (plain/int8/rolling/GQA) — rolling caches
    # can't ROLL BACK, but their verify writes must still match steps.
    ("verify_cache_parity", check_verify_cache_parity, lambda c: c.decoder_only),
    # The prefix cache refuses rolling-window caches (absolute-position
    # rows are evicted on wrap), so the restore/prefill structural parity
    # applies to every OTHER LM cache variant: plain, int8, GQA.
    (
        "prefix_restore_parity",
        check_prefix_restore_parity,
        lambda c: c.decoder_only and not c.attention_window,
    ),
    # The paged pool refuses rolling windows for the same reason the
    # prefix cache does; every other LM cache variant must gather views
    # dense-identical and keep the pool layout stable across restore and
    # prefill.
    (
        "paged_alias_parity",
        check_paged_alias_parity,
        lambda c: c.decoder_only and not c.attention_window,
    ),
    ("softmax_f32", check_softmax_f32, lambda c: True),
    ("residual_dtype", check_residual_dtype, lambda c: True),
    ("mask_broadcast", check_mask_broadcast, lambda c: True),
    ("decode_shapes", check_decode_shapes, lambda c: not c.encoder_only),
    ("train_step_dtypes", check_train_step_dtypes, lambda c: True),
    ("telemetry_inert", check_telemetry_inert, lambda c: True),
    # Fault injection serves the continuous-batching (decoder-only) tier;
    # the armed/disarmed jaxpr identity covers its two hot-path shapes.
    ("fault_plane_inert", check_fault_plane_inert, lambda c: c.decoder_only),
]


def run_contracts(matrix_name: str = "fast") -> list[ContractResult]:
    """Trace every applicable (contract, config) pair; failures are captured
    as results, never raised (the CLI exits non-zero when any ``ok`` is
    False)."""
    results: list[ContractResult] = []
    for cfg_name, cfg in matrix(matrix_name).items():
        for contract_name, fn, applies in _CONTRACTS:
            if not applies(cfg):
                continue
            try:
                detail = fn(cfg)
                ok = True
            except AssertionError as e:
                detail, ok = str(e), False
            results.append(
                ContractResult(
                    contract=contract_name, config=cfg_name, ok=ok, detail=detail
                )
            )
    return results


def summarize(results: list[ContractResult]) -> str:
    failed = [r for r in results if not r.ok]
    lines = [str(r) for r in (failed or results)]
    lines.append(
        f"{len(results) - len(failed)}/{len(results)} contracts hold"
        + ("" if not failed else f" — {len(failed)} FAILED")
    )
    return "\n".join(lines)
