"""What the ``serve_sessions`` kind needs of the system under test beyond
``perfbench/program_api.py``: a scheduler whose prefix cache is on, as
``cli.serve --prefix_cache_mb N --prefix_block B`` builds it, and the cache's
own counts. Like ``program_api.py``, the one place a rename in the program
is followed.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from transformer_tpu.config import PAD_ID
from transformer_tpu.obs.telemetry import Telemetry
from transformer_tpu.serve import scheduler as _sched
from transformer_tpu.serve.prefix_cache import PrefixCache

from perfbench.program_api import IdTokenizer, model_config


def make_scheduler(params, config: dict, deployment: dict, prefix_cache: dict, span_tap):
    """``ContinuousScheduler`` with a ``PrefixCache`` of ``prefix_cache["budget_mb"]``
    MB on the host, in blocks of the pool's ``kv_block`` positions: on the paged
    layout the scheduler adopts a retiring request's prompt blocks into the
    cache where they lie (the device tier) and spills to the host budget only
    under the pool's pressure."""
    cfg = model_config(config)
    cache = PrefixCache(cfg, block_tokens=deployment.get("kv_block", 16), budget_mb=int(prefix_cache["budget_mb"]))
    tel = Telemetry(interval=1e12)
    sched = _sched.ContinuousScheduler(
        params, cfg, IdTokenizer(), telemetry=tel, span_tap=span_tap, prefix_cache=cache, **deployment
    )
    return sched, tel


def prefix_tokens(sched) -> dict:
    """The scheduler's running totals of prompt tokens admitted, of those
    served from the cache, and of those aliased from pool blocks the cache holds."""
    return {k: int(sched.stats.get(k, 0)) for k in ("prompt_tokens", "prefix_hit_tokens", "prefix_alias_tokens")}


def _request(ids: list[int], max_new: int) -> dict:
    """The request whose prompt the scheduler reads as ``ids``: it puts the
    BOS token before a prompt's text itself."""
    if ids[0] != IdTokenizer.bos_id:
        raise ValueError("a prompt as the model reads it begins with the BOS token")
    return {"prompt": " ".join(map(str, ids[1:])), "max_new": max_new}


def answer(sched, ids: list[int], max_new: int) -> list[int]:
    """One request through the scheduler, run until it answers: the tokens it
    answered. ``ids`` is the prompt as the model reads it, BOS first. The
    pool is idle before and after; the prompt's blocks are in the cache
    after."""
    sched.submit(_request(ids, max_new))
    while sched.busy:
        sched.admit()
        sched.step()
    (resp,) = sched.drain_ready()
    return [int(t) for t in resp["continuation"].split()]


def admitted_logits(sched, ids: list[int], steps: int, before_steps=None) -> dict:
    """``ids`` (BOS first) admitted by the scheduler's own admission (the
    cache's match, the aliased blocks, the suffix's prefill at its offset),
    then fed one position a step through the pool program the scheduler
    dispatches: the prompt's tail that the prefill left, then ``steps``
    greedy tokens.

    Returns the float32 logits of each position fed (P, V), the first
    position fed, the tokens fed, what the slot's blocks hold after the last
    step (``slot_rows``: every position fed and those before), and the prompt
    tokens the cache served and aliased. ``before_steps(sched, slot)``, where
    given, runs between the admission and the first step, and what it
    returns, where not None, after the last. The scheduler then steps the
    request to its end itself, so the pool is left idle."""
    stats0 = prefix_tokens(sched)
    order = sched.submit(_request(ids, 2))
    sched.admit()
    slot, st = next((s, a) for s, a in sched._active.items() if a.order == order)
    undo = before_steps(sched, slot) if before_steps is not None else None
    cfg, pool = sched.cfg, sched.pool
    first, tok, n = st.pos, st.cur, len(ids) - st.pos + steps
    sched._paged_ensure(slot, first + n)
    toks = np.full((sched.num_slots,), PAD_ID, np.int32)
    index = np.zeros((sched.num_slots,), np.int32)
    rows, fed = [], []
    for k in range(n):
        toks[slot], index[slot] = tok, first + k
        fed.append(int(tok))
        logits, pool.caches = _sched._pool_step_paged_flash(
            sched.params, pool.caches, pool.alloc.table_device(), jnp.asarray(index),
            jnp.asarray(toks), cfg, pool.block_tokens, sched._kernel_interpret,
        )
        rows.append(np.asarray(logits[slot], np.float32))
        nxt = first + k + 1
        tok = ids[nxt] if nxt < len(ids) else int(rows[-1].argmax())
    cached = slot_rows(sched, slot, first + n)
    if undo is not None:
        undo()
    while sched.busy:
        sched.admit()
        sched.step()
    sched.drain_ready()
    stats1 = prefix_tokens(sched)
    return {"logits": np.stack(rows), "first": first, "fed": fed, "rows": cached,
            "hit": stats1["prefix_hit_tokens"] - stats0["prefix_hit_tokens"],
            "aliased": stats1["prefix_alias_tokens"] - stats0["prefix_alias_tokens"]}


def slot_rows(sched, slot: int, positions: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """What the slot's blocks hold for positions 0 .. ``positions`` - 1, read
    through its block table as the kernels read it: for each layer (keys,
    values) in float32, each (positions, KV heads, head size)."""
    pos = np.arange(positions)
    blocks = jnp.asarray(sched.pool.alloc.table[slot][pos // sched.pool.block_tokens])
    offsets = jnp.asarray(pos % sched.pool.block_tokens)
    return [(np.asarray(layer["k"][blocks, offsets], np.float32), np.asarray(layer["v"][blocks, offsets], np.float32))
            for layer in sched.pool.caches]
