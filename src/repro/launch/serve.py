"""Serving driver: batched prefill + decode with KV caches / SSM states.

Decoder-only archs serve SPLIT by default now — the ``Federation``
session's serve plane (``fed.decode``) keeps the training party split at
inference: client parties embed their token spans, the server owns
backbone + head + caches, and every step's wire traffic (one embedding
up, token ids down) lands in the Transport's ledger. The pre-session
global path survives as the back-compat shim (``n_clients=0``) and the
fallback for families that cannot cross the VFL wire (encoder-decoder /
VLM need a modality frontend on the wire).

On CPU this serves the reduced configs (the ``serve_decode`` example);
the same step functions are what the dry-run lowers for ``decode_32k`` /
``long_500k`` on the production mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --reduced \
        --batch 4 --prompt-len 16 --gen-len 16 [--clients 2]
    PYTHONPATH=src python -m repro.launch.serve --full --layers 8 \
        --continuous --batch 8 --prompt-len 128 --gen-len 32  # one TPU v5e
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import driver_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import common
from repro.models.model_api import build_cache_specs, build_model


def _zero_caches(cfg, batch: int, seq: int):
    specs = build_cache_specs(cfg, batch, seq)
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)), specs,
        is_leaf=lambda x: hasattr(x, "logical"))


def _splittable(cfg) -> bool:
    return not (cfg.is_encoder_decoder or cfg.family == "vlm")


def serve_config(arch: str, *, use_reduced: bool = True,
                 n_layers: int = 0):
    """The config :func:`serve` runs: the driver's config without remat
    (decode has no backward pass to recompute for)."""
    return dataclasses.replace(
        driver_config(arch, use_reduced=use_reduced, n_layers=n_layers),
        remat=False)


def serve(arch: str, *, batch: int = 4, prompt_len: int = 16,
          gen_len: int = 16, use_reduced: bool = True, n_layers: int = 0,
          seed: int = 0, temperature: float = 0.0, n_clients: int = 0,
          continuous: bool = False, max_batch: int = 4,
          max_queue: int = None, preempt: bool = False,
          n_pages: int = None, deadline: int = None) -> dict:
    """``n_clients >= 1`` routes through the session's split serve plane
    (falling back to the global path for families that cannot split);
    ``n_clients=0`` is the pre-session global decode, bit-identical to
    the split path on replicated client tables. ``continuous=True``
    serves ``batch`` independent requests through the continuous-batching
    scheduler (``fed.serve``) over ``max_batch`` slots instead of one
    fused batch — with the failure policy exposed: ``max_queue`` bounds
    admission (the driver drains on :class:`QueueFull` and retries),
    ``preempt``/``n_pages`` enable page-pool preemption under memory
    pressure, and ``deadline`` gives every request that many scheduler
    steps to retire (expired requests come back ``status="deadline"``).
    ``use_reduced=False`` keeps the published widths; ``n_layers`` > 0
    replaces the depth alone."""
    cfg = serve_config(arch, use_reduced=use_reduced, n_layers=n_layers)
    if n_clients and _splittable(cfg):
        if continuous:
            res = _serve_continuous(arch, cfg, batch=batch,
                                    prompt_len=prompt_len, gen_len=gen_len,
                                    seed=seed, temperature=temperature,
                                    n_clients=n_clients,
                                    max_batch=max_batch,
                                    max_queue=max_queue, preempt=preempt,
                                    n_pages=n_pages, deadline=deadline)
        else:
            res = _serve_federated(arch, cfg, batch=batch,
                                   prompt_len=prompt_len, gen_len=gen_len,
                                   seed=seed, temperature=temperature,
                                   n_clients=n_clients)
    else:
        res = _serve_global(arch, cfg, batch=batch, prompt_len=prompt_len,
                            gen_len=gen_len, seed=seed,
                            temperature=temperature)
        if n_clients:
            res["fallback"] = (f"{cfg.family}/encdec family needs a "
                               "modality frontend on the wire; served "
                               "global")
    # the depth and widths that actually ran
    res["model"] = {k: getattr(cfg, k) for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
        "vocab_size")}
    return res


# ------------------------------------------------- split (session) path ---

def _build_session(cfg, *, n_clients: int, prompt_len: int, gen_len: int,
                   seed: int):
    """(fed, key, params) for a serving run — the party span split is
    rounded up to cover the full served window."""
    from repro.federation import Federation
    max_seq = prompt_len + gen_len
    seq_len = -(-max_seq // n_clients) * n_clients
    fed = Federation.build(cfg, n_clients=n_clients, seq_len=seq_len)
    key = jax.random.key(seed)
    params = common.materialize(fed.model.param_specs, key)
    return fed, key, params


def request_prompts(key, batch: int, prompt_len: int, vocab_size: int):
    """The continuous path's prompts, (batch, prompt_len) int32 on the
    host: request i draws from ``fold_in(key, 1000 + i)``. All are drawn
    in one batched device op and fetched with a single transfer."""
    return np.asarray(jax.vmap(
        lambda i: jax.random.randint(jax.random.fold_in(key, 1000 + i),
                                     (prompt_len,), 0, vocab_size))(
                                         jnp.arange(batch)))


def _serve_federated(arch: str, cfg, *, batch: int, prompt_len: int,
                     gen_len: int, seed: int, temperature: float,
                     n_clients: int) -> dict:
    fed, key, params = _build_session(cfg, n_clients=n_clients,
                                      prompt_len=prompt_len,
                                      gen_len=gen_len, seed=seed)
    toks = jax.random.randint(jax.random.fold_in(key, 1),
                              (batch, prompt_len), 0, cfg.vocab_size)
    res = fed.decode(params, toks, gen_len=gen_len,
                     temperature=temperature, key=key)
    gen = res.tokens
    assert gen.shape == (batch, gen_len)
    assert np.isfinite(np.asarray(res.logits, np.float32)).all()
    return {
        "arch": arch, "batch": batch, "mode": "federated",
        "clients": n_clients,
        "prompt_len": prompt_len, "gen_len": gen_len,
        "prefill_s": round(res.prefill_s, 2),
        "compile_s": round(res.compile_s, 2),
        "decode_tok_per_s": round(batch * gen_len
                                  / max(res.decode_s, 1e-9), 1),
        "wire_bytes": res.wire_bytes,
        "wire_has_gradients": res.transmits_gradients,
        "sample_output": gen[0, :8].tolist(),
    }


# ------------------------------------------- continuous-batching path ---

def _serve_continuous(arch: str, cfg, *, batch: int, prompt_len: int,
                      gen_len: int, seed: int, temperature: float,
                      n_clients: int, max_batch: int,
                      max_queue: int = None, preempt: bool = False,
                      n_pages: int = None, deadline: int = None) -> dict:
    from repro.federation import QueueFull
    fed, key, params = _build_session(cfg, n_clients=n_clients,
                                      prompt_len=prompt_len,
                                      gen_len=gen_len, seed=seed)
    srv = fed.serve(params, max_batch=max_batch, temperature=temperature,
                    max_queue=max_queue, preempt=preempt, n_pages=n_pages)
    prompts = request_prompts(key, batch, prompt_len, cfg.vocab_size)
    queue_retries = 0
    for i in range(batch):
        while True:
            try:
                srv.submit(prompts[i], gen_len,
                           key=jax.random.fold_in(key, i),
                           deadline=deadline)
                break
            except QueueFull:
                # bounded admission is recoverable by design: drain a
                # block, then offer the request again
                queue_retries += 1
                srv.run(max_steps=1)
    srv.run()
    results = [srv.results[rid] for rid in sorted(srv.results)]
    assert len(results) == batch
    ok = [r for r in results if r.status == "ok"]
    total_tokens = sum(r.tokens.size for r in ok)
    statuses = {}
    for r in results:
        statuses[r.status] = statuses.get(r.status, 0) + 1
    return {
        "arch": arch, "batch": batch, "mode": "continuous",
        "clients": n_clients, "slots": max_batch,
        "prompt_len": prompt_len, "gen_len": gen_len,
        "steps": srv.steps,
        "compile_s": round(srv.compile_s, 2),
        "decode_tok_per_s": round(total_tokens / max(srv.last_run_s, 1e-9),
                                  1),
        "statuses": statuses,
        "admitted": srv.admitted,
        "prefill_waves": srv.prefill_waves,
        "preemptions": srv.preemptions,
        "deadline_misses": srv.deadline_misses,
        "queue_retries": queue_retries,
        "wire_bytes": sum(r.wire_bytes for r in results),
        "wire_has_gradients": any(r.transmits_gradients for r in results),
        "sample_output": (ok[0] if ok else results[0]).tokens[:8].tolist(),
        # every request's generated ids, in rid order
        "tokens": [r.tokens.tolist() for r in results],
    }


# ---------------------------------------------- global back-compat shim ---

def _serve_global(arch: str, cfg, *, batch: int, prompt_len: int,
                  gen_len: int, seed: int, temperature: float) -> dict:
    max_seq = prompt_len + gen_len
    model = build_model(cfg, max_seq=max_seq)
    key = jax.random.key(seed)
    params = common.materialize(model.param_specs, key)

    toks = jax.random.randint(jax.random.fold_in(key, 1),
                              (batch, prompt_len), 0, cfg.vocab_size)
    caches = _zero_caches(cfg, batch, max_seq)
    decode = jax.jit(model.decode_fn, donate_argnums=(2,))

    extra = {}
    if cfg.is_encoder_decoder:
        from repro.models import encdec
        frames = jnp.zeros((batch, cfg.encoder_seq, cfg.frontend_dim),
                           jnp.bfloat16)
        extra["enc_out"] = encdec.encode(cfg, params, frames)

    t0 = time.time()
    # prefill: feed prompt tokens through the decode path one at a time
    # (prefill-as-decode; the batched prefill program is exercised by the
    # prefill_32k dry-run shape)
    logits = None
    for t in range(prompt_len):
        logits, caches = decode(params, {"tokens": toks[:, t:t + 1], **extra},
                                caches, t)
    t_prefill = time.time() - t0

    out_tokens = []
    t0 = time.time()
    for t in range(prompt_len, max_seq):
        lg = logits[:, -1].astype(jnp.float32)
        if temperature > 0:
            nxt = jax.random.categorical(
                jax.random.fold_in(key, 100 + t), lg / temperature, axis=-1)
        else:
            nxt = jnp.argmax(lg, axis=-1)
        nxt = jnp.minimum(nxt, cfg.vocab_size - 1).astype(jnp.int32)
        # tokens stay on device; the host sees ONE (B, gen_len) fetch
        # after the loop instead of gen_len per-token syncs
        out_tokens.append(nxt)
        logits, caches = decode(params, {"tokens": nxt[:, None], **extra},
                                caches, t)
    jax.block_until_ready(logits)
    t_decode = time.time() - t0

    gen = np.asarray(jnp.stack(out_tokens, axis=1))
    assert gen.shape == (batch, gen_len)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    return {
        "arch": arch, "batch": batch, "mode": "global",
        "prompt_len": prompt_len, "gen_len": gen_len,
        "prefill_s": round(t_prefill, 2),
        "decode_tok_per_s": round(batch * gen_len / max(t_decode, 1e-9), 1),
        "sample_output": gen[0, :8].tolist(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b", choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--reduced", action="store_true", default=True)
    # published widths (no reduced()); pair with --layers to fit a chip
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--layers", type=int, default=0,
                    help="replace the config's depth only (0 = keep it)")
    # 0 = the pre-session global path; >=1 serves split via fed.decode
    ap.add_argument("--clients", type=int, default=2)
    # continuous batching: drain --batch requests through --max-batch slots
    ap.add_argument("--continuous", action="store_true")
    ap.add_argument("--max-batch", type=int, default=4)
    # failure policy (continuous path only): bounded admission, page-pool
    # preemption, and a per-request step deadline
    ap.add_argument("--max-queue", type=int, default=None)
    ap.add_argument("--preempt", action="store_true")
    ap.add_argument("--n-pages", type=int, default=None)
    ap.add_argument("--deadline", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()
    print(json.dumps(serve(args.arch, batch=args.batch,
                           prompt_len=args.prompt_len, gen_len=args.gen_len,
                           temperature=args.temperature,
                           use_reduced=args.reduced,
                           n_layers=args.layers,
                           n_clients=args.clients,
                           continuous=args.continuous,
                           max_batch=args.max_batch,
                           max_queue=args.max_queue,
                           preempt=args.preempt,
                           n_pages=args.n_pages,
                           deadline=args.deadline), indent=2))


if __name__ == "__main__":
    main()
