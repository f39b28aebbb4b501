"""The serve plane: split inference with the training party split.

Training never merges the parties — and neither does serving. The OWNING
client party (position ``t`` belongs to client ``t // span``, the same
span split the training adapter uses) embeds tokens on its own
parameters and uploads embeddings; the server holds the backbone, head
and every KV/SSM cache, and returns only sampled token ids. Logits,
caches and activations never cross the wire, and every step's
uplink/downlink lands in the session's :class:`repro.core.privacy.Ledger`
through the ``Transport`` — serve-time traffic is accounted exactly like
training rounds.

Throughput comes from three compiled layers (the per-token,
Python-dispatched loop of the first serve plane survives only as the
fallback/oracle):

* **scan decode** — the whole generation is ONE ``jax.lax.scan``: tokens
  are sampled on device inside the scan body (``fold_in`` keys per step,
  same stream as the eager loop), accumulated on device, and transferred
  to the host once at the end. Bitwise-equal to the per-token loop —
  which stays bitwise-equal to global decode.
* **chunked prefill** — each owning client embeds its WHOLE span of the
  prompt in one ``client_embed`` call and the server consumes the
  ``(B, chunk, d_model)`` upload through the adapter's ``server_prefill``
  hook (one compiled pass per span instead of one dispatch per token).
  Adapters without the hook fall back to the step loop.
* **AOT compile separation** — every program is lowered + compiled
  explicitly (memoized in ``_AOT_CACHE``), so ``prefill_s``/``decode_s``
  time pure execution and ``compile_s`` reports compilation honestly
  (the bench warm-up keys off this).

Continuous batching over these pieces lives in
:mod:`repro.federation.scheduler`.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import weakref
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import marks, tags
from repro.core.adapters import ModelAdapter
from repro.core.privacy import Ledger


@dataclasses.dataclass
class ServeResult:
    """One ``Federation.decode`` call: generated tokens + wire totals."""
    tokens: np.ndarray              # (B, gen_len) sampled token ids
    logits: jnp.ndarray             # final-step logits (B, 1, vocab) —
                                    # server-side state, exposed for tests
    ledger: Ledger
    prefill_s: float = 0.0          # pure execution (outputs blocked on)
    decode_s: float = 0.0           # pure execution (outputs blocked on)
    compile_s: float = 0.0          # AOT compilation, reported separately

    @property
    def wire_bytes(self) -> int:
        return self.ledger.total_bytes

    @property
    def transmits_gradients(self) -> bool:
        return self.ledger.transmits_gradients


# ============================================== compiled-program cache =====

# AOT executables memoized on (jitted fn, argument signature): timing must
# report compile separately from run, and jit's internal cache would fold
# the first compile into the first timed call. Keyed on abstract shapes so
# a serving loop (or the continuous scheduler) reuses executables across
# requests exactly like the old lru-cached jit did. LRU-bounded: a
# long-lived server cycling through many (prompt_len, gen_len) signatures
# must not accumulate executables forever.
_AOT_CACHE: Dict = {}
_AOT_CACHE_MAX = 256

# Signature memo for big containers (param trees): the old _sig flattened
# the FULL params pytree on every lookup — hundreds of leaves walked per
# serve step just to discover the same signature again. A container's
# signature is now memoized on its id(), guarded by (type, len) and a
# weakref to its first leaf: identity of the container plus identity of
# its first leaf pins the same live tree (a dead tree whose id got reused
# fails the anchor check, because its leaves died with it). Trees are
# treated as immutable once built — true for params/caches here, which
# are only ever REPLACED (donation returns fresh containers), never
# mutated in place.
_TREE_SIG_MEMO: Dict[int, Tuple] = {}
_TREE_SIG_MEMO_MAX = 512
_SIG_STATS = {"flattens": 0, "memo_hits": 0}


def _leaf_sig(leaf):
    if isinstance(leaf, (bool, int, float)):
        return ("py", type(leaf).__name__)
    return ("leaf", tuple(leaf.shape), str(leaf.dtype))


def _first_leaf(obj):
    for _ in range(64):
        if isinstance(obj, dict):
            if not obj:
                return None
            obj = obj[next(iter(obj))]
        elif isinstance(obj, (list, tuple)):
            if not obj:
                return None
            obj = obj[0]
        else:
            return obj
    return obj


def _container_sig(obj) -> Tuple:
    oid = id(obj)
    anchor = _first_leaf(obj)
    memo = _TREE_SIG_MEMO.get(oid)
    if memo is not None:
        ref, guard, sig = memo
        if guard == (type(obj), len(obj)) and (
                ref() is anchor if ref is not None else anchor is None):
            _SIG_STATS["memo_hits"] += 1
            return sig
    _SIG_STATS["flattens"] += 1
    leaves, treedef = jax.tree.flatten(obj)
    sig = (treedef, tuple(_leaf_sig(x) for x in leaves))
    try:
        ref = weakref.ref(anchor) if anchor is not None else None
    except TypeError:
        ref = None
    while len(_TREE_SIG_MEMO) >= _TREE_SIG_MEMO_MAX:
        del _TREE_SIG_MEMO[next(iter(_TREE_SIG_MEMO))]
    _TREE_SIG_MEMO[oid] = (ref, (type(obj), len(obj)), sig)
    return sig


def _sig(args) -> Tuple:
    out = []
    for a in args:
        if isinstance(a, (bool, int, float)):
            out.append(("py", type(a).__name__))
        elif isinstance(a, (dict, list, tuple)):
            out.append(("tree", _container_sig(a)))
        elif hasattr(a, "shape") and hasattr(a, "dtype"):
            out.append(_leaf_sig(a))
        else:
            _SIG_STATS["flattens"] += 1
            leaves, treedef = jax.tree.flatten(a)
            out.append(("tree", (treedef,
                                 tuple(_leaf_sig(x) for x in leaves))))
    return tuple(out)


# XLA for the TPU rewrites a dot with few lhs rows (a small decode step)
# as a multiply and a reduction on the vector unit, while a larger batch
# runs the same dot on the MXU. The two sum in different orders, so a
# request's greedy tokens would depend on how many requests share its
# step, and on a TPU v5e at phi3-mini widths the paged decode block and
# the dense decode scan left each other after 13 tokens. Every
# serve-plane program keeps its dots on the MXU instead.
_BATCH_INVARIANT_OPTIONS = {
    "tpu": {"xla_tpu_enable_dot_strength_reduction": False}}


def compiled_with_timing(jitted, *args):
    """(compiled_executable, compile_seconds) — 0.0 on a cache hit."""
    key = (jitted, _sig(args))
    hit = _AOT_CACHE.pop(key, None)
    if hit is not None:
        _AOT_CACHE[key] = hit          # refresh recency: dict order is the
        return hit, 0.0                # LRU list, eviction takes the front
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile(
        _BATCH_INVARIANT_OPTIONS.get(jax.default_backend()))
    dt = time.perf_counter() - t0
    while len(_AOT_CACHE) >= _AOT_CACHE_MAX:
        del _AOT_CACHE[next(iter(_AOT_CACHE))]
    _AOT_CACHE[key] = compiled
    return compiled, dt


def _require_serve_plane(adapter: ModelAdapter):
    if adapter.client_embed is None or adapter.server_decode is None:
        raise ValueError(
            f"adapter {adapter.name!r} has no serve plane (client_embed/"
            "server_decode hooks); build the session from a ModelConfig "
            "to serve split inference")


# ===================================================== compiled steps ======

@functools.lru_cache(maxsize=32)
def make_serve_step(adapter: ModelAdapter, n_clients: int, seq_len: int):
    """Jitted one-token split-inference step.

    ``step(params, tok, caches, t)``: the client owning position ``t``
    embeds ``tok`` (one dynamic gather into the stacked client params —
    the other parties' tables are never read), the server decodes against
    its caches. Compiled once; ``t`` is a traced scalar. lru-cached on
    (adapter, split) like the engine's ``_make_runner``, so a serving
    loop calling ``fed.decode`` per request reuses the compiled step
    instead of retracing the backbone every call (adapters are frozen
    value objects and the adapter factories are themselves cached, so
    equal configs hit)."""
    _require_serve_plane(adapter)
    span = seq_len // n_clients

    @tags.wire("up", accounted_by="Transport.account_serve", kind="embedding",
               reason="split-inference uplink: the owning client's one-token "
                      "embedding; logits and caches stay server-side")
    def step(params, tok, caches, t):
        m = t // span
        client_m = jax.tree.map(lambda a: a[m], params["clients"])
        e = marks.wire_boundary(adapter.client_embed(client_m, tok),
                                kind="emb", direction="up")
        logits, caches = adapter.server_decode(params["server"], e, caches,
                                               t)
        return logits, caches

    return jax.jit(step, donate_argnums=(2,))


@functools.lru_cache(maxsize=32)
def make_prefill_chunk(adapter: ModelAdapter, n_clients: int, seq_len: int):
    """Jitted chunked-prefill step: client ``m`` embeds its whole
    ``(B, chunk)`` span slice in ONE call and the server consumes the
    ``(B, chunk, d_model)`` upload through ``server_prefill``. Returns
    only the last position's logits (the decode seed), the caches and the
    backbone's stats (``server_prefill``'s); one compile per distinct
    chunk length."""
    _require_serve_plane(adapter)
    if adapter.server_prefill is None:
        raise ValueError(
            f"adapter {adapter.name!r} has no server_prefill hook; use the "
            "per-token step loop")

    @tags.wire("up", accounted_by="Transport.account_serve", kind="embedding",
               reason="chunked-prefill uplink: one whole span embedding per "
                      "chunk; prefill carries no downlink")
    def chunk(params, toks, caches, t0, m):
        with jax.named_scope("serve.prefill"):
            client_m = jax.tree.map(lambda a: a[m], params["clients"])
            e = marks.wire_boundary(adapter.client_embed(client_m, toks),
                                    kind="emb", direction="up")
            logits, caches, stats = adapter.server_prefill(
                params["server"], e, caches, t0)
        return logits[:, -1:], caches, stats

    return jax.jit(chunk, donate_argnums=(2,))


@functools.lru_cache(maxsize=64)
def make_decode_scan(adapter: ModelAdapter, n_clients: int, seq_len: int,
                     prompt_len: int, gen_len: int, temperature: float,
                     vocab_size: int):
    """The whole generation as ONE compiled ``lax.scan``.

    Per step the body samples on device from the carried logits (same
    ``fold_in(key, 100 + t)`` stream and clamp as the eager loop — the
    paths are bitwise-interchangeable), hands the token to the owning
    client, and steps the server. Sampled tokens are scan outputs, so the
    host sees ONE (B, gen_len) transfer at the end instead of gen_len
    per-token syncs."""
    _require_serve_plane(adapter)
    span = seq_len // n_clients

    def run(params, logits0, caches, key):
        @tags.wire("up", accounted_by="Transport.account_serve",
                   kind="embedding",
                   reason="scan-compiled decode: per-step one-token uplink, "
                          "token ids come back as scan outputs")
        def body(carry, t):
            logits, caches = carry
            # the serve plane's only downlink: one sampled token id per
            # step to the owning client (never the logits)
            nxt = marks.wire_boundary(
                sample_token(logits, key, t, temperature, vocab_size),
                kind="token", direction="down")
            m = t // span
            client_m = jax.tree.map(lambda a: a[m], params["clients"])
            e = marks.wire_boundary(adapter.client_embed(client_m,
                                                         nxt[:, None]),
                                    kind="emb", direction="up")
            logits, caches = adapter.server_decode(params["server"], e,
                                                   caches, t)
            return (logits, caches), nxt

        (logits, caches), toks = jax.lax.scan(
            body, (logits0, caches),
            jnp.arange(prompt_len, prompt_len + gen_len))
        return toks.T, logits, caches               # (gen_len, B) -> (B, T)

    return jax.jit(run, donate_argnums=(2,))


def prefill_plan(prompt_len: int, span: int) -> List[Tuple[int, int, int]]:
    """Span-aligned chunk schedule ``[(t0, t1, owner_m)]`` covering the
    prompt: each chunk lies inside exactly one client party's span, so
    one party embeds it in one call."""
    plan = []
    t0 = 0
    while t0 < prompt_len:
        m = t0 // span
        t1 = min((m + 1) * span, prompt_len)
        plan.append((t0, t1, m))
        t0 = t1
    return plan


# The fewest batch rows a serve-plane program runs. On a TPU v5e a row's
# prefill and decode-step logits were bitwise the same at 4 and 8 rows,
# but not at 1 or 2: XLA drops a unit batch dimension, and tiles a small
# one otherwise, and either then reduces in another order. Smaller
# batches are padded with copies of their first row, so one request
# decodes the same tokens alone and batched.
MIN_ROWS = 4


def pad_rows(x, rows: int):
    """``x`` with copies of its first row appended up to ``rows`` rows."""
    return jnp.concatenate([x, jnp.repeat(x[:1], rows - x.shape[0], 0)])


def zero_caches(adapter: ModelAdapter, batch: int, max_seq: int):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)),
        adapter.cache_specs(batch, max_seq),
        is_leaf=lambda x: hasattr(x, "logical"))


def sample_token(logits, key, t, temperature, vocab_size):
    """THE serve-plane sampler: greedy, or categorical on the
    ``fold_in(key, 100 + t)`` stream. Pure jnp, so the eager fallback
    loop, the decode-scan body and the continuous scheduler's slot step
    all call this one function — the bitwise solo == scan == continuous
    guarantee hangs on there being exactly one implementation.
    ``temperature`` must be a static Python float; ``t`` may be traced."""
    lg = logits[:, -1].astype(jnp.float32)
    if temperature > 0:
        nxt = jax.random.categorical(
            jax.random.fold_in(key, 100 + t), lg / temperature, axis=-1)
    else:
        nxt = jnp.argmax(lg, axis=-1)
    return jnp.minimum(nxt, vocab_size - 1).astype(jnp.int32)


# ============================================================ run_decode ===

def run_decode(adapter: ModelAdapter, transport, *, n_clients: int,
               seq_len: int, embed_dim: int, vocab_size: int, params,
               prompts, gen_len: int, temperature: float = 0.0,
               key=None, ledger: Optional[Ledger] = None,
               use_scan: bool = True,
               chunked_prefill: bool = True) -> ServeResult:
    """Prefill + decode through the split serve plane (the
    ``Federation.decode`` engine).

    ``use_scan=False`` / ``chunked_prefill=False`` select the per-token
    step loop (the equivalence oracle; the fallback loop still keeps
    sampled tokens on device and transfers once at the end)."""
    prompts = jnp.asarray(prompts, jnp.int32)
    B, prompt_len = prompts.shape
    prompts = pad_rows(prompts, max(B, MIN_ROWS))
    max_seq = prompt_len + gen_len
    if max_seq > seq_len:
        raise ValueError(
            f"prompt_len + gen_len = {max_seq} exceeds the session "
            f"seq_len {seq_len} (the party span split is sized to it)")
    if key is None:
        key = jax.random.key(0)
    span = seq_len // n_clients
    step = make_serve_step(adapter, n_clients, seq_len)
    caches = zero_caches(adapter, prompts.shape[0], max_seq)
    compile_s = 0.0
    chunked = chunked_prefill and adapter.server_prefill is not None

    # ------------------------------------------------------- prefill ----
    if chunked:
        chunk_fn = make_prefill_chunk(adapter, n_clients, seq_len)
        plan = prefill_plan(prompt_len, span)
        progs = []
        for t0, t1, m in plan:
            prog, dt = compiled_with_timing(
                chunk_fn, params, prompts[:, t0:t1], caches, t0, m)
            compile_s += dt
            progs.append(prog)
        tic = time.perf_counter()
        logits = None
        for (t0, t1, m), prog in zip(plan, progs):
            logits, caches, _ = prog(params, prompts[:, t0:t1], caches, t0,
                                     m)
        jax.block_until_ready(logits)
        prefill_s = time.perf_counter() - tic
    else:
        cstep, dt = compiled_with_timing(step, params, prompts[:, :1],
                                         caches, 0)
        compile_s += dt
        tic = time.perf_counter()
        logits = None
        for t in range(prompt_len):
            logits, caches = cstep(params, prompts[:, t:t + 1], caches, t)
        jax.block_until_ready(logits)
        prefill_s = time.perf_counter() - tic

    # -------------------------------------------------------- decode ----
    if use_scan:
        scan_fn = make_decode_scan(adapter, n_clients, seq_len, prompt_len,
                                   gen_len, float(temperature), vocab_size)
        prog, dt = compiled_with_timing(scan_fn, params, logits, caches, key)
        compile_s += dt
        tic = time.perf_counter()
        toks_dev, logits, caches = prog(params, logits, caches, key)
        out_tokens = np.asarray(jax.block_until_ready(toks_dev))
        decode_s = time.perf_counter() - tic
    else:
        cstep, dt = compiled_with_timing(step, params, prompts[:, :1],
                                         caches, prompt_len)
        compile_s += dt
        out = []
        tic = time.perf_counter()
        for t in range(prompt_len, max_seq):
            nxt = sample_token(logits, key, t, temperature, vocab_size)
            out.append(nxt)        # stays on device; one transfer at the end
            logits, caches = cstep(params, nxt[:, None], caches, t)
        out_tokens = np.asarray(
            jax.block_until_ready(jnp.stack(out, axis=1)))
        decode_s = time.perf_counter() - tic

    # every decode call uploads one embedding; only the gen_len sampled
    # tokens cross back down (the clients already hold the prompt)
    ledger = transport.account_serve(batch=B, embed=embed_dim,
                                     n_steps=max_seq, n_gen=gen_len,
                                     ledger=ledger)
    return ServeResult(tokens=out_tokens[:B], logits=logits[:B],
                       ledger=ledger, prefill_s=prefill_s,
                       decode_s=decode_s, compile_s=compile_s)
