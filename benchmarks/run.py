"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per measured cell).

  bench_attack              — Table I   (direct label-inference attack)
  bench_convergence_clients — Fig 3 / Table II-left  (M ∈ {4,6,8})
  bench_server_width        — Fig 5a / Table II-mid  (width ∈ {128,256,512})
  bench_hparam_robustness   — Fig 4    (lr sensitivity: cascaded vs ZOO-VFL)
  bench_large_model         — Fig 5b/c (split LM at laptop scale)
  bench_wire                — §II communication efficiency (bytes/round)
  bench_kernels             — kernel microbench (XLA-path oracle timing)
  bench_zoo_fanout          — stacked vs unrolled ZOO fan-out, q ∈ {1,4,16}
  bench_lm_async            — reduced transformer server under the async
                              engine via Federation, q ∈ {1,4} + DP point
  bench_serve_throughput    — fused split-serve engine: seed per-token
                              loop vs scan decode vs batched vs continuous
                              batching (emits BENCH_serve.json)
  bench_wire_faults         — population engine over the wire plane:
                              throughput + bytes/round vs drop/latency
                              (emits BENCH_wire.json)
  bench_serve_chaos         — serve-plane failure policy: goodput vs
                              preemption, deadline misses, kill-mid-drain
                              recovery, poison isolation
                              (emits BENCH_chaos.json)
  bench_roofline            — §Roofline terms from the dry-run artifacts

``BENCH_*.json`` artifacts keep a dated history entry per run (see
``benchmarks.history``) instead of being overwritten.

Run: PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fast]

Every bench runs in this one process; an exception in any of them ends
the run with a non-zero exit. The device-sharded block-size sweep over 8
virtual CPU devices is a separate CPU-only command,
``python -m benchmarks.async_scale``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache

ROWS = []


def row(name: str, us_per_call: float, derived: str):
    line = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(line)
    print(line, flush=True)


def _time(fn, *args, n=10, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


# ======================================================== Table I ==========

def bench_attack(fast: bool):
    from repro.core import attacks
    n = 512 if fast else 2048
    for fw in ("foo", "zoo"):
        t0 = time.perf_counter()
        r = attacks.run_label_inference(jax.random.key(0), 10, n,
                                        framework=fw)
        us = (time.perf_counter() - t0) / n * 1e6
        row(f"attack_{fw}", us,
            f"curious={r.curious_client_acc:.3f};eaves={r.eavesdropper_acc:.3f}")


# ============================================== Fig 3 / Table II-left ======

def _tabular_setup(n_clients, server_embed=64, n=2048, f=64, c=10):
    from repro.configs.paper_mlp import PaperMLPConfig
    from repro.data import make_classification, vertical_partition
    from repro.models import common, tabular
    cfg = PaperMLPConfig(n_features=f, n_classes=c, n_clients=n_clients,
                         client_embed=32, server_embed=server_embed)
    X, y = make_classification(0, n, f, c)
    Xp = jnp.asarray(vertical_partition(X, n_clients))
    params = common.materialize(tabular.param_specs(cfg), jax.random.key(0))
    return cfg, Xp, jnp.asarray(y), params


# per-method (lr chosen by the paper's style of grid search; ZOO methods
# need the much smaller lr — reproducing the paper's Fig 4 observation)
LRS = {"cascaded": 0.05, "vafl": 0.05, "split": 0.05,
       "zoo-vfl": 0.001, "syn-zoo": 0.001}


def _run_engine(method, params, Xp, y, steps, lr):
    from repro.configs import VFLConfig
    from repro.core import async_engine
    from repro.models import tabular
    vfl = VFLConfig(mu=1e-3, lr_server=lr, lr_client=lr)
    t0 = time.perf_counter()
    res = async_engine.run(
        async_engine.EngineConfig(method=method, steps=steps, batch_size=64),
        vfl, params, Xp, y)
    us = (time.perf_counter() - t0) / steps * 1e6
    acc = float(tabular.accuracy(res.params, Xp, y))
    return us, acc, res


def bench_convergence_clients(fast: bool):
    steps = 300 if fast else 1500
    for m_clients in (4, 6, 8):
        cfg, Xp, y, params = _tabular_setup(m_clients)
        for method in ("split", "vafl", "syn-zoo", "zoo-vfl", "cascaded"):
            us, acc, _ = _run_engine(method, params, Xp, y, steps,
                                     LRS[method])
            row(f"clients{m_clients}_{method}", us, f"train_acc={acc:.3f}")


# ============================================== Fig 5a / Table II-mid ======

def bench_server_width(fast: bool):
    steps = 300 if fast else 1500
    for width in (128, 256, 512):
        cfg, Xp, y, params = _tabular_setup(4, server_embed=width)
        for method in ("vafl", "zoo-vfl", "cascaded"):
            us, acc, _ = _run_engine(method, params, Xp, y, steps,
                                     LRS[method])
            row(f"width{width}_{method}", us, f"train_acc={acc:.3f}")


# ======================================================== Fig 4 ============

def bench_hparam_robustness(fast: bool):
    steps = 300 if fast else 1000
    cfg, Xp, y, params = _tabular_setup(4)
    for method in ("cascaded", "zoo-vfl"):
        accs = []
        for lr in (0.02, 0.01, 0.005, 0.001):
            us, acc, _ = _run_engine(method, params, Xp, y, steps, lr)
            accs.append(acc)
            row(f"lr{lr}_{method}", us, f"train_acc={acc:.3f}")
        row(f"lr_spread_{method}", 0.0,
            f"acc_min={min(accs):.3f};acc_max={max(accs):.3f}")


# ===================================================== Fig 5b/c ============

def bench_large_model(fast: bool):
    """Split-LM analogue of the ResNet/distilBERT experiments: the same
    global model trained with cascaded vs full-ZOO vs (unsafe) split."""
    from repro.launch.train import train
    steps = 100 if fast else 300
    for method, lr in (("split-learning", 0.05), ("cascaded", 0.05),
                       ("zoo-vfl", 0.003)):
        res = train("phi3-mini-3.8b", steps=steps, batch=8, seq=64,
                    method=method, lr=lr, log_every=10 ** 9)
        us = 1e6 / max(res["steps_per_s"], 1e-9)
        row(f"lm_{method}", us,
            f"loss_drop={res['loss_first'] - res['loss_last']:.3f};"
            f"wire_grad={res['wire_has_gradients']}")


# ================================================== wire accounting ========

def bench_wire(fast: bool):
    from repro.core.privacy import Ledger
    for method in ("cascaded", "zoo-vfl", "vafl", "split-learning"):
        led = Ledger()
        led.log_round(method, 64, 128)
        row(f"wire_{method}", 0.0,
            f"bytes={led.total_bytes};grads={led.transmits_gradients}")


# ======================================================== kernels ==========

def bench_kernels(fast: bool):
    from repro.kernels.flash_attention.ref import flash_attention_ref
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    from repro.kernels.zoo_dual_matmul.ref import zoo_dual_matmul_ref
    k = jax.random.key(0)
    q = jax.random.normal(k, (4, 512, 64), jnp.bfloat16)
    us = _time(jax.jit(lambda a: flash_attention_ref(a, a, a)), q)
    flops = 4 * 4 * 512 * 512 * 64
    row("flash_attention_ref", us, f"gflops={flops / us / 1e3:.1f}")

    x = jax.random.normal(k, (2048, 1024), jnp.bfloat16)
    sc = jnp.ones(1024)
    us = _time(jax.jit(lambda a, s: rmsnorm_ref(a, s)), x, sc)
    row("rmsnorm_ref", us, f"gbps={2 * x.size * 2 / us / 1e3:.1f}")

    w = jax.random.normal(k, (1024, 1024), jnp.bfloat16)
    u = jax.random.normal(k, (1024, 1024), jnp.bfloat16)
    us = _time(jax.jit(lambda a, b, c: zoo_dual_matmul_ref(a, b, c, 1e-3)),
               x, w, u)
    row("zoo_dual_matmul_ref", us,
        f"gflops={2 * 2 * 2048 * 1024 * 1024 / us / 1e3:.1f}")

    from repro.kernels.ssd_chunk.ref import ssd_chunk_ref
    BH, S, P, N = 8, 1024, 64, 32
    xh = jax.random.normal(k, (BH, S, P), jnp.float32)
    a = jnp.full((BH, S), 0.9)
    dt = jnp.ones((BH, S))
    bm = jax.random.normal(k, (BH, S, N), jnp.float32)
    us = _time(jax.jit(lambda *t: ssd_chunk_ref(*t)), xh, a, dt, bm, bm, n=3)
    row("ssd_chunk_ref", us, f"tokens_per_s={BH * S / us * 1e6:.0f}")


# ==================================================== ZOO fan-out ==========

def bench_zoo_fanout(fast: bool):
    from benchmarks.zoo_fanout import bench_zoo_fanout as bench
    bench(fast, row=row)


# ================================================== LM async engine ========

def bench_lm_async(fast: bool):
    from benchmarks.lm_async import bench_lm_async as bench
    bench(fast, row=row)


# ================================================ serve throughput =========

def bench_serve_throughput(fast: bool):
    from benchmarks.serve_throughput import \
        bench_serve_throughput as bench
    bench(fast, row=row)


# ================================================ wire fault sweep =========

def bench_wire_faults(fast: bool):
    from benchmarks.wire_faults import bench_wire_faults as bench
    bench(fast, row=row)


# ================================================== serve chaos ============

def bench_serve_chaos(fast: bool):
    from benchmarks.serve_chaos import bench_serve_chaos as bench
    bench(fast, row=row)


# ======================================================== roofline =========

def bench_roofline(fast: bool):
    """Re-derive the §Roofline table from the dry-run artifacts."""
    pat = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun", "*baseline.json")
    files = sorted(glob.glob(pat))
    if not files:
        row("roofline_missing", 0.0, "run repro.launch.dryrun first")
        return
    for f in files:
        with open(f) as fh:
            res = json.load(fh)
        if "skipped" in res or res.get("mesh") != "16x16":
            continue
        r = res["roofline"]
        row(f"roofline_{res['arch']}_{res['shape']}",
            r["step_time_s"] * 1e6,
            f"bound={r['bottleneck']};compute_ms={r['compute_s']*1e3:.1f};"
            f"memory_ms={r['memory_s']*1e3:.1f};"
            f"coll_ms={r['collective_s']*1e3:.1f};mfu={r['mfu']:.3f}")


BENCHES = {
    "attack": bench_attack,
    "convergence_clients": bench_convergence_clients,
    "server_width": bench_server_width,
    "hparam_robustness": bench_hparam_robustness,
    "large_model": bench_large_model,
    "wire": bench_wire,
    "kernels": bench_kernels,
    "zoo_fanout": bench_zoo_fanout,
    "lm_async": bench_lm_async,
    "serve_throughput": bench_serve_throughput,
    "wire_faults": bench_wire_faults,
    "serve_chaos": bench_serve_chaos,
    "roofline": bench_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(BENCHES))
    ap.add_argument("--fast", action="store_true", default=True)
    ap.add_argument("--full", dest="fast", action="store_false")
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        fn(args.fast)


if __name__ == "__main__":
    main()
