"""Persistent serving loop: JSONL requests on stdin, JSONL responses on stdout.

    python -m transformer_tpu.cli.serve --export_path=model \
        --src_vocab_file=src.subwords --tgt_vocab_file=tgt.subwords

Each input line is either a JSON object or a raw sentence:

    {"src": "he goes to school"}            seq2seq translation
    {"src": "...", "beam": 4}               per-request beam override
    {"prompt": "...", "max_new": 32}        decoder-only LM continuation
    {"fill": "he [MASK] to school"}         encoder-only masked-LM fill
    he goes to school                       raw line == {"src": ...}
                                            (or prompt/fill per export kind)

One response line per request: {"translation": ...} / {"continuation": ...}
/ {"filled": ..., "candidates": ...}, or {"error": ...} for malformed requests (the loop never dies on one bad
line). Responses come back in request order.

Two levels of amortization make this the right shape for a long-lived TPU
process:

- **Compile caching**: the decode program caches per (batch, width) bucket,
  so request N hits the cache request 1 paid for (vs one `cli.translate`
  process per request, which recompiles every time).
- **Request batching**: a reader thread queues stdin lines; each loop
  iteration drains up to ``--serve_batch`` ALREADY-QUEUED requests (never
  waits for stragglers — an idle queue means a batch of 1 and zero added
  latency), groups them by decode signature (kind + max_len + beam /
  sampling params), and runs ONE decode per group. Concurrent clients
  share the chip instead of serializing through batch-1 decodes.

Decoder-only (LM) exports additionally get **continuous batching**
(``--serve_slots``, default on): instead of decoding each drained batch to
completion, a step-level scheduler advances a fixed pool of KV-cache slots
one token per tick, retiring finished requests and admitting queued ones
mid-flight via single-pass chunked prefill (``--prefill_chunk``) — a
straggler with a long generation no longer holds a whole batch's chip time
hostage. ``--serve_slots=0`` restores the grouped decode-to-completion
path. ``--speculate_k`` adds speculative decoding on the same slot pool:
a drafter (``--draft_checkpoint`` model or the default n-gram
prompt-lookup, ``--draft_ngram``) proposes candidate tokens and one
multi-token verify forward scores them all — more tokens per
bandwidth-bound forward, byte-identical greedy answers.
``--prefix_cache_mb`` adds a cross-request prefix KV cache: completed
prompt KV is kept host-side in a radix trie of token-aligned blocks
(``--prefix_block``), and a new request restores its longest shared
prefix straight into its slot instead of re-forwarding it — shared
system prompts and retry storms stop paying prefill. See
docs/SERVING.md.

Telemetry: ``--metrics_jsonl`` streams structured events (per-request spans,
slot utilization) + periodic metric snapshots, and ``--metrics_port`` serves
a Prometheus ``/metrics`` scrape endpoint — docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import json
import queue
import sys
import threading
import time

from absl import app, flags, logging

FLAGS = flags.FLAGS


def define_serve_flags() -> None:
    from transformer_tpu.cli.flags import define_metrics_flags
    from transformer_tpu.cli.translate import define_export_serving_flags

    define_export_serving_flags()
    define_metrics_flags()
    flags.DEFINE_integer(
        "serve_batch", 8,
        "max already-queued requests aggregated into one decode (grouped by "
        "decode signature; 1 = the old request-at-a-time behavior)")
    flags.DEFINE_integer(
        "serve_slots", 8,
        "KV-cache slots for continuous (in-flight) batching of decoder-only "
        "LM requests: finished requests retire at step boundaries and queued "
        "ones are admitted mid-flight via chunked prefill. 0 = grouped "
        "decode-to-completion batching (the --serve_batch path). Ignored for "
        "seq2seq / fill-mask exports, which always use the grouped path.")
    flags.DEFINE_integer(
        "serve_max_total", 0,
        "per-slot KV budget (prompt + generated tokens) for continuous "
        "batching; 0 sizes it to the model's max_position")
    flags.DEFINE_integer(
        "prefill_chunk", 0,
        "split prompt prefill into chunks of this many tokens so activation "
        "memory stays bounded at long prompt lengths (0 = whole prompt in "
        "one forward); also used by grouped-path generate()")
    flags.DEFINE_integer(
        "speculate_k", 0,
        "speculative decoding lookahead for the continuous-batching path: "
        "a drafter proposes up to this many candidate tokens per step and "
        "one multi-token verify forward scores them all (greedy answers "
        "stay byte-identical; sampled requests use rejection-sampling "
        "acceptance). 0 = off. Incompatible with attention_window "
        "(rolling caches cannot roll back)")
    flags.DEFINE_string(
        "draft_checkpoint", "",
        "export directory of a small draft model SHARING the target "
        "tokenizer, used as the speculative drafter ('' = the model-free "
        "n-gram prompt-lookup drafter)")
    flags.DEFINE_integer(
        "draft_ngram", 3,
        "longest suffix n-gram the model-free drafter matches against "
        "earlier context (only used when --draft_checkpoint is unset)")
    flags.DEFINE_integer(
        "prefix_cache_mb", 0,
        "host-memory byte budget (MiB) for the cross-request prefix KV "
        "cache on the continuous-batching path: completed prompt KV is "
        "stored as token-aligned blocks in a radix trie and new requests "
        "restore their longest shared prefix instead of re-forwarding it "
        "(greedy answers byte-identical). 0 = off. Incompatible with "
        "attention_window (rolling caches evict absolute-position rows)")
    flags.DEFINE_integer(
        "prefix_block", 16,
        "prefix-cache block granularity in tokens: prompts share stored KV "
        "in units of this many positions (smaller = finer matching, more "
        "trie overhead)")
    flags.DEFINE_boolean(
        "prefix_verify_checksums", True,
        "re-verify each matched prefix-cache block's crc32 at admission "
        "(corrupt blocks are dropped instead of silently restored — "
        "docs/ROBUSTNESS.md). Costs O(matched KV bytes) of host CPU per "
        "hit; disable to trade integrity checking for admission latency")
    flags.DEFINE_enum(
        "kv_layout", "dense", ["dense", "paged"],
        "per-slot KV storage for the continuous-batching path: 'dense' "
        "reserves max_total rows per slot (the historical layout); "
        "'paged' backs every slot from ONE device-resident block pool "
        "through per-slot block tables (kernels/kv_pool.py) — resident KV "
        "proportional to used tokens, prefix-cache hits restored by "
        "block-table aliasing with zero host copies, byte-identical "
        "answers either way. Incompatible with attention_window")
    flags.DEFINE_integer(
        "kv_pool_blocks", 0,
        "paged KV pool size in blocks of --prefix_block tokens (0 = full "
        "provisioning: every slot can always reach --serve_max_total). "
        "Smaller pools bound resident KV by used tokens; under pressure "
        "the device-resident prefix tier spills to host and, as the last "
        "rung, the requesting slot answers a structured 'resource' error")
    flags.DEFINE_enum(
        "decode_kernel", "xla", ["xla", "paged_flash"],
        "decode/verify kernel for the paged continuous-batching path: "
        "'xla' gathers a dense view of each slot's KV through the block "
        "table (the bitwise parity reference and CPU fallback); "
        "'paged_flash' runs the fused Pallas kernels that read pool "
        "blocks in place (no gathered view) plus the fused "
        "residual+LN+FFN step — requires --kv_layout paged, a "
        "decoder-only config without attention_window; answers are "
        "byte-identical to 'xla'. Off-TPU backends run the kernels in "
        "Pallas interpret mode (a correctness path, not a fast one)")
    flags.DEFINE_integer(
        "max_backlog", 0,
        "bounded admission backpressure for the continuous-batching path: "
        "submissions beyond this many queued-but-unadmitted requests answer "
        "a structured 'backpressure' error immediately instead of growing "
        "the queue (0 = unbounded, the historical behavior)")
    flags.DEFINE_integer(
        "admission_retries", 2,
        "bounded retries (with jittered exponential backoff) for transient "
        "admission faults on the continuous-batching path; exhausted "
        "retries answer a structured 'transient' error")
    flags.DEFINE_integer(
        "breaker_threshold", 3,
        "consecutive faults before a serving circuit breaker (speculative "
        "decoding / prefix cache) fails its subsystem open to the plain "
        "byte-parity path — docs/ROBUSTNESS.md")
    flags.DEFINE_float(
        "breaker_cooldown", 30.0,
        "seconds an open circuit breaker waits before one half-open "
        "re-probe of its subsystem")
    flags.DEFINE_string(
        "fault_spec", "",
        "deterministic fault injection for chaos drills (docs/ROBUSTNESS.md "
        "grammar), e.g. 'serve.prefill:p=0.25,seed=7;obs.emit:at=5'. "
        "'' = disarmed (zero overhead)")
    flags.DEFINE_string(
        "slo_spec", "",
        "SLO objectives evaluated as multi-window burn rates over the "
        "answer stream (docs/OBSERVABILITY.md grammar), e.g. "
        "'availability:objective=0.999;ttft_p95:threshold=0.5'. '' = the "
        "default objectives when telemetry is on; 'none' = off. Surfaced "
        "as serve_slo_burn_* gauges + slo.burn events; report offline with "
        "`python -m transformer_tpu.obs slo <jsonl>`")


def _parse_line(line: str, model_cfg) -> dict:
    """One stdin line -> request dict (raises on malformed input)."""
    if line.startswith("{"):
        req = json.loads(line)
        if not isinstance(req, dict):
            raise ValueError("request must be a JSON object")
        return req
    # Raw-line convenience maps to whichever request kind this export serves.
    if model_cfg.encoder_only:
        return {"fill": line}
    return {"prompt" if model_cfg.decoder_only else "src": line}


def _signature(
    req: dict, model_cfg, default_max_len: int, default_beam: int
) -> tuple | None:
    """Batching key: requests in the same group run as ONE decode call.
    None = malformed or kind-mismatched (answered individually)."""
    if model_cfg.encoder_only:
        if "fill" not in req:
            return None
        top_k = int(req.get("top_k", 5))
        if not 1 <= top_k <= 100:
            # Raised (not returned) so the caller's except answers THIS
            # request with the message instead of a routing error.
            raise ValueError(f"top_k must be in [1, 100], got {top_k}")
        return ("fill", top_k)
    # Non-MLM exports ignore a stray 'fill' key (unknown keys never
    # changed routing before the fill kind existed).
    if "src" in req:
        if model_cfg.decoder_only:
            return None
        return (
            "src",
            int(req.get("max_len", default_max_len)),
            int(req.get("beam", default_beam)),
        )
    if "prompt" in req:
        if not model_cfg.decoder_only:
            return None
        temperature = float(req.get("temperature", 0.0))
        return (
            "prompt",
            int(req.get("max_new", default_max_len)),
            temperature,
            int(req.get("top_k", 0)),
            float(req.get("top_p", 1.0)),
            # Per-request sampling seed: part of the signature because one
            # generate() call holds ONE rng for the whole batch (the
            # continuous scheduler honors seeds per-request; grouped serving
            # must answer seeded requests identically). Greedy decode never
            # touches the rng, so a stray seed must not split its groups.
            int(req.get("seed", 0)) if temperature > 0.0 else 0,
        )
    return None


def serve_lines(
    lines: list[str], params, model_cfg, src_tok, tgt_tok,
    default_max_len: int = 64, default_beam: int = 1,
    prefill_chunk: int = 0,
) -> list[dict]:
    """Answer a batch of request lines with one decode per signature group,
    preserving input order. Pure function of its inputs — the unit the
    batching test drives directly."""
    from transformer_tpu.train.decode import fill_mask, generate, translate

    responses: list[dict | None] = [None] * len(lines)
    groups: dict[tuple, list[tuple[int, dict]]] = {}
    kind = (
        "fill-mask" if model_cfg.encoder_only
        else "LM" if model_cfg.decoder_only else "seq2seq"
    )
    served_key = {"fill-mask": "fill", "LM": "prompt", "seq2seq": "src"}[kind]
    for i, line in enumerate(lines):
        try:
            req = _parse_line(line, model_cfg)
            # int()/float() on request fields can raise too ("beam": "four"):
            # inside the try so one bad request answers, never kills the loop.
            sig = _signature(req, model_cfg, default_max_len, default_beam)
        except Exception as e:  # noqa: BLE001 — bad line answers, never kills
            responses[i] = {"error": f"{type(e).__name__}: {e}"}
            continue
        if sig is not None and sig[0] == "prompt" and sig[2] > 0.0:
            # Sampled LM requests run batch-1: one lm_generate rng serves a
            # whole batch, so a co-batched sampled request's draws would
            # depend on its neighbors — the answer to a seeded request must
            # not change with traffic (and must match the continuous
            # scheduler, which picks per-row).
            sig = (*sig, i)
        if sig is None:
            sent = next(
                (k for k in ("src", "prompt", "fill") if k in req), None
            )
            if sent:
                msg = f"{kind} export serves '{served_key}', not '{sent}'"
            else:
                msg = (
                    "request needs 'src' (seq2seq), 'prompt' (LM) or "
                    "'fill' (masked-LM)"
                )
            responses[i] = {"error": msg}
            continue
        groups.setdefault(sig, []).append((i, req))

    def run_group(sig, members) -> list[dict]:
        if sig[0] == "fill":
            _, top_k = sig
            outs = fill_mask(
                params, model_cfg, tgt_tok,
                [str(req["fill"]) for _, req in members],
                top_k=top_k,
            )
            # Tuples -> lists for clean JSON round-trips.
            return [
                {
                    "filled": o["filled"],
                    "candidates": [
                        [[t, p] for t, p in cands] for cands in o["candidates"]
                    ],
                }
                for o in outs
            ]
        if sig[0] == "src":
            _, max_len, beam = sig
            outs = translate(
                params, model_cfg, src_tok, tgt_tok,
                [str(req["src"]) for _, req in members],
                max_len=max_len, beam_size=beam,
            )
            return [{"translation": out} for out in outs]
        # Sampled signatures carry a trailing per-request discriminator
        # (batch-1 semantics above) — slice the decode params off the front.
        _, max_new, temperature, top_k, top_p, seed = sig[:6]
        outs = generate(
            params, model_cfg, tgt_tok,
            [str(req["prompt"]) for _, req in members],
            max_new=max_new, temperature=temperature,
            top_k=top_k, top_p=top_p, seed=seed, prefill_chunk=prefill_chunk,
        )
        return [{"continuation": out} for out in outs]

    for sig, members in groups.items():
        try:
            outs = run_group(sig, members)
        except Exception:  # noqa: BLE001
            # One request can poison a whole group (e.g. an over-length
            # prompt). Preserve per-request error isolation: retry each
            # member alone so innocent co-batched requests still succeed.
            outs = []
            for member in members:
                try:
                    outs.extend(run_group(sig, [member]))
                except Exception as e:  # noqa: BLE001 — answers, never kills
                    outs.append({"error": f"{type(e).__name__}: {e}"})
        for (i, _), out in zip(members, outs):
            responses[i] = out
    return [
        r if r is not None else {"error": "internal: unanswered"}
        for r in responses
    ]


class _RoutingError(ValueError):
    """Kind-mismatch the grouped path answers with the BARE message (its
    sig-is-None branch builds the response directly, no exception-type
    prefix) — serve_continuous must answer it the same way."""


def _route_lm_request(line: str, model_cfg) -> dict:
    """One stdin line -> LM request dict for the continuous scheduler
    (raises with the same message shapes ``serve_lines`` answers with)."""
    req = _parse_line(line, model_cfg)
    # Mirror _signature's key precedence exactly — 'src' rejects even when
    # 'prompt' is also present, a stray 'fill' next to 'prompt' is ignored —
    # so --serve_slots=0 and the continuous path answer any given line the
    # same way.
    if "src" in req:
        raise _RoutingError("LM export serves 'prompt', not 'src'")
    if "prompt" not in req:
        if "fill" in req:
            raise _RoutingError("LM export serves 'prompt', not 'fill'")
        raise _RoutingError(
            "request needs 'src' (seq2seq), 'prompt' (LM) or "
            "'fill' (masked-LM)"
        )
    return req


def serve_continuous(q: queue.Queue, sched, model_cfg, telemetry=None) -> None:
    """Drive the continuous-batching scheduler from the stdin queue: ingest
    whatever is already queued (malformed lines answer immediately via a
    reserved output position — ordering is preserved), admit queued requests
    into free slots, advance every occupied slot one token, flush responses
    completed in arrival order. Blocks on stdin ONLY when nothing is
    in-flight and nothing is waiting to flush — an in-flight request never
    waits on a quiet client. Ingestion stops while the scheduler's backlog
    plus its unflushed responses reach the cap, so the reader thread's
    bounded queue keeps exerting stdin backpressure (a piped multi-GB
    request file must not accumulate in the scheduler's host-side queue —
    and a flood of instantly error-answered lines must not accumulate in
    its done-buffer — either)."""
    eof = False
    backlog_cap = max(1, sched.num_slots) * 8
    while not eof or sched.busy:
        while not eof and sched.backlog + sched.ready_count < backlog_cap:
            try:
                line = q.get(block=not (sched.busy or sched.has_ready))
            except queue.Empty:
                break
            if line is None:
                eof = True
                break
            line = line.strip()
            if not line:
                continue
            try:
                req = _route_lm_request(line, model_cfg)
            except _RoutingError as e:
                # Structured error codes (docs/ROBUSTNESS.md) ride along; the
                # `error` string stays byte-identical to the grouped path's.
                sched.submit_done({"error": str(e), "code": "routing"})
                continue
            except Exception as e:  # noqa: BLE001 — bad line answers, never kills
                sched.submit_done(
                    {"error": f"{type(e).__name__}: {e}", "code": "validation"}
                )
                continue
            sched.submit(req)
        sched.admit()
        sched.step()
        sched.idle_backoff()
        for resp in sched.drain_ready():
            print(json.dumps(resp), flush=True)
    if telemetry is not None:
        telemetry.maybe_flush(force=True)




def main(argv) -> None:
    del argv
    from transformer_tpu.cli.flags import flags_to_telemetry, maybe_force_platform

    maybe_force_platform()
    if FLAGS.fault_spec:
        # Arm the fault plane BEFORE any subsystem starts: injection points
        # fire deterministically per (seed, point, call-index), so a chaos
        # drill replays exactly (docs/ROBUSTNESS.md).
        from transformer_tpu.serve import resilience

        resilience.install(resilience.FaultPlane.parse(FLAGS.fault_spec))
        logging.info("fault plane armed: %s", FLAGS.fault_spec)
    telemetry = flags_to_telemetry()
    if FLAGS.slo_spec and FLAGS.slo_spec.lower() not in ("none", "off") \
            and telemetry is None:
        # The engine's whole output is gauges + slo.burn events: without a
        # telemetry sink an explicit spec would silently enforce nothing.
        logging.warning(
            "--slo_spec needs --metrics_jsonl (or --metrics_port) to "
            "surface burn rates; SLO evaluation disabled for this run"
        )

    from transformer_tpu.cli.translate import load_export
    from transformer_tpu.data.tokenizer import SubwordTokenizer

    params, model_cfg = load_export(
        FLAGS.export_path, kv_cache_int8=FLAGS.kv_cache_int8
    )
    if model_cfg.decoder_only or model_cfg.encoder_only:
        src_tok = tgt_tok = SubwordTokenizer.load(FLAGS.tgt_vocab_file)
    else:
        src_tok = SubwordTokenizer.load(FLAGS.src_vocab_file)
        tgt_tok = (
            src_tok
            if FLAGS.tgt_vocab_file == FLAGS.src_vocab_file
            else SubwordTokenizer.load(FLAGS.tgt_vocab_file)
        )
    continuous = model_cfg.decoder_only and FLAGS.serve_slots > 0
    logging.info(
        "serving %s from %s; one JSONL request per stdin line, %s",
        "fill-mask" if model_cfg.encoder_only
        else "LM" if model_cfg.decoder_only else "seq2seq",
        FLAGS.export_path,
        f"continuous batching over {FLAGS.serve_slots} cache slots"
        if continuous
        else f"batching up to {max(1, FLAGS.serve_batch)} queued requests "
        "per decode",
    )

    # Bounded queue: the reader thread blocks on put() once it is this far
    # ahead, restoring the stdin backpressure a blocking read loop has — a
    # piped multi-GB request file must not accumulate in host memory.
    from transformer_tpu.serve.replica import stdin_reader

    q: queue.Queue = queue.Queue(maxsize=max(1, FLAGS.serve_batch) * 8)
    threading.Thread(target=stdin_reader, args=(q,), daemon=True).start()
    if continuous:
        from transformer_tpu.obs.slo import DEFAULT_SLOS
        from transformer_tpu.serve import (
            ContinuousScheduler,
            PrefixCache,
            drafter_from_flags,
        )

        drafter = None
        if FLAGS.speculate_k > 0:
            drafter = drafter_from_flags(
                FLAGS.draft_checkpoint, FLAGS.draft_ngram,
                FLAGS.serve_max_total or model_cfg.max_position + 1,
                eos_id=tgt_tok.eos_id,
                target_vocab_size=model_cfg.target_vocab_size,
            )
        prefix_cache = None
        if FLAGS.prefix_cache_mb > 0:
            prefix_cache = PrefixCache(
                model_cfg,
                block_tokens=FLAGS.prefix_block,
                budget_mb=FLAGS.prefix_cache_mb,
                verify_checksums=FLAGS.prefix_verify_checksums,
            )
        # Price the pool before allocating it: the cost model's dense-KV
        # budget (analysis/costs.py — the number the paged-KV refactor is
        # measured against) in the startup log, so an operator sees the
        # device bytes a --serve_slots/--serve_max_total choice commits to.
        from transformer_tpu.analysis.costs import kv_cache_bytes

        # Same sizing as the scheduler's SlotPool: max_total plus the
        # speculative lookahead slack (verify rows write k extra rows).
        pool_tokens = (
            FLAGS.serve_max_total or model_cfg.max_position + 1
        ) + max(0, FLAGS.speculate_k)
        kv = kv_cache_bytes(model_cfg, pool_tokens)
        if FLAGS.kv_layout == "paged":
            blk = FLAGS.prefix_block
            slot_blocks = -(-pool_tokens // blk)
            n_blocks = FLAGS.kv_pool_blocks or (
                1 + FLAGS.serve_slots * slot_blocks
            )
            pool_bytes = n_blocks * blk * kv["bytes_per_token"]
            logging.info(
                "paged KV pool budget: %d blocks x %d tokens = %.1f MiB "
                "(%d bytes/token; dense layout would reserve %.1f MiB)",
                n_blocks, blk, pool_bytes / (1 << 20),
                kv["bytes_per_token"],
                FLAGS.serve_slots * kv["bytes_per_slot"] / (1 << 20),
            )
        else:
            logging.info(
                "slot pool KV budget: %d slots x %d bytes/slot = %.1f MiB "
                "(%d bytes/token, dense max_len layout)",
                FLAGS.serve_slots, kv["bytes_per_slot"],
                FLAGS.serve_slots * kv["bytes_per_slot"] / (1 << 20),
                kv["bytes_per_token"],
            )
        sched = ContinuousScheduler(
            params, model_cfg, tgt_tok,
            num_slots=FLAGS.serve_slots,
            max_total=FLAGS.serve_max_total or None,
            prefill_chunk=FLAGS.prefill_chunk,
            default_max_new=FLAGS.max_len,
            telemetry=telemetry,
            speculate_k=FLAGS.speculate_k,
            drafter=drafter,
            prefix_cache=prefix_cache,
            max_backlog=FLAGS.max_backlog,
            kv_layout=FLAGS.kv_layout,
            kv_block=FLAGS.prefix_block,
            kv_pool_blocks=FLAGS.kv_pool_blocks,
            decode_kernel=FLAGS.decode_kernel,
            admission_retries=FLAGS.admission_retries,
            breaker_threshold=FLAGS.breaker_threshold,
            breaker_cooldown_s=FLAGS.breaker_cooldown,
            # '' = the default objective set (only consulted when telemetry
            # is on — the engine's whole output is gauges + events);
            # 'none' parses to an empty tuple and disables it.
            slos=FLAGS.slo_spec or (DEFAULT_SLOS if telemetry else None),
        )
        serve_continuous(q, sched, model_cfg, telemetry=telemetry)
        if telemetry is not None:
            telemetry.close()
        return
    eof = False
    while not eof:
        first = q.get()
        if first is None:
            break
        lines = [first]
        # Drain whatever is ALREADY queued (no waiting: an idle queue means
        # a batch of one and zero added latency).
        while len(lines) < max(1, FLAGS.serve_batch):
            try:
                nxt = q.get_nowait()
            except queue.Empty:
                break
            if nxt is None:
                eof = True
                break
            lines.append(nxt)
        lines = [line.strip() for line in lines]
        lines = [line for line in lines if line]
        if not lines:
            continue
        t0 = time.perf_counter()
        responses = serve_lines(
            lines, params, model_cfg, src_tok, tgt_tok,
            default_max_len=FLAGS.max_len, default_beam=FLAGS.beam,
            prefill_chunk=FLAGS.prefill_chunk,
        )
        if telemetry is not None:
            # Grouped path: one span per drained batch (the per-request
            # breakdown is the continuous scheduler's richer contract).
            batch_s = time.perf_counter() - t0
            errors = sum(1 for r in responses if "error" in r)
            reg = telemetry.registry
            reg.counter("serve_requests_total").inc(len(responses))
            if errors:
                reg.counter("serve_errors_total").inc(errors)
            reg.histogram(
                "serve_batch_seconds", "one grouped decode batch"
            ).observe(batch_s)
            telemetry.emit(
                "serve.batch", size=len(responses), errors=errors,
                batch_s=round(batch_s, 6),
            )
            telemetry.maybe_flush()
        for resp in responses:
            print(json.dumps(resp), flush=True)
    if telemetry is not None:
        telemetry.close()


def run() -> None:
    define_serve_flags()
    app.run(main)


if __name__ == "__main__":
    run()
