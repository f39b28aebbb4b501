"""Smoke run of the system's main paths on one TPU chip.

One process, nothing else on the chip. At the published widths of
phi3-mini-3.8b (arXiv:2404.14219: hidden 3072, 32 heads, 32 KV heads,
FFN 8192, vocabulary 32064, bf16), cut in depth to ``Plan.layers``, it
runs these phases in order:

  device  JAX must report a TPU; anything else fails at once.
  train   ``repro.launch.train.train``: cascaded steps (ZOO client, FOO
          server) at batch 8 x seq 512, q = 4; finite losses and no
          gradient on the wire.
  async   ``Federation.build(...).run(...)``: the paper's asynchronous
          protocol over 2 client parties with the fused client lanes.
  kernel  the paper's tabular job (``PaperMLPConfig``, M = 4) through the
          async engine with the Pallas ``zoo_dual_matmul_stacked`` lanes
          and with the XLA lanes: the step must hold ``tpu_custom_call``
          (the kernel compiled, not interpreted) and both must agree.
  serve   ``repro.launch.serve.serve(continuous=True)``: 8 requests over
          4 slots all retire ``ok``, and one request's greedy tokens
          equal its solo ``fed.decode`` (split == global, bitwise).

``--four-chips`` runs only the device-sharded client block
(``EngineConfig(mesh_shards=4)``) against ``mesh_shards=1``.

Seconds and bytes printed here are smoke figures, not benchmark numbers.
Any failure exits non-zero; the last line, on success only, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

    python chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
ARCH = "phi3-mini-3.8b"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# The two tabular comparisons run both sides at full f32 matmul precision
# (the tabular model is float32; a TPU's default matmul takes one bf16
# pass), and with Gaussian ZOO directions. With the unit-sphere
# directions the loss difference a lane makes, μ·<∇, u> with |u| = 1 over
# ~25k client weights, is about one f32 ulp of the loss, and φ/μ = d/μ
# scales that ulp into the client update: two programs that round the
# lanes 1e-5 apart then drift ~1e-2 in loss within 8 rounds (CPU,
# lanes jittered on purpose). Gaussian directions (φ = 1, |u| ~ √d) lift
# the difference ~100x above the ulp.
TABULAR_PRECISION = "highest"
TABULAR_ZOO_DIST = "normal"
# Pallas vs XLA lanes on the same inputs: both are f32 products, so the
# lanes agree to f32 rounding (relative 1e-5 of their norm). Their
# perturbation parts (lane - clean lane) / μ magnify the f32 rounding of
# the lanes by 1/μ = 1e3, to about 1e-4 of their norm; a wrong lane,
# bias or μ changes either by O(1).
LANES_RTOL = 1e-5
DIRECTION_RTOL = 1e-3
# Per-round loss of two engine runs that differ in how the same f32
# values are computed (kernel vs XLA, or 4 shards vs 1). On the CPU,
# lanes jittered by a relative 1e-5 move these losses by at most 4.4e-5
# over 8 rounds, while clients that never learn (lanes with no signal)
# move them by 0.12. The bound sits between the two.
LOSS_ATOL = 1e-3


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sizes of one smoke run; the defaults are what the chip runs."""
    use_reduced: bool = False       # False keeps every published width
    layers: int = 8                 # a quarter of the published 32
    batch: int = 8
    seq: int = 512
    zoo_queries: int = 4
    clients: int = 2
    train_steps: int = 5
    async_rounds: int = 3
    async_rows: int = 16
    requests: int = 8
    prompt_len: int = 128
    gen_len: int = 32
    slots: int = 4
    tabular_clients: int = 4
    tabular_batch: int = 256        # the kernel's x is (256, 196) f32
    tabular_rows: int = 1024
    tabular_rounds: int = 8
    sharded_block: int = 4


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileClock:
    """Sums the backend compile seconds JAX reports while it listens."""

    def __init__(self) -> None:
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration


def lm_config(plan: Plan):
    from repro.configs import driver_config
    return driver_config(ARCH, use_reduced=plan.use_reduced,
                         n_layers=plan.layers)


# ------------------------------------------------------------- phases ---

def phase_train(plan: Plan) -> dict:
    from repro.launch.train import train
    res = train(ARCH, steps=plan.train_steps, batch=plan.batch,
                seq=plan.seq, method="cascaded",
                zoo_queries=plan.zoo_queries,
                use_reduced=plan.use_reduced, n_layers=plan.layers,
                log_every=1)
    # loss_last is the mean of the last 5 steps: finite only if each is
    check(math.isfinite(res["loss_first"]) and math.isfinite(
        res["loss_last"]), f"train losses not finite: {res}")
    check(res["wire_has_gradients"] is False,
          "the cascaded wire carried gradients")
    return {"loss_first": res["loss_first"], "loss_last": res["loss_last"]}


def phase_async(plan: Plan) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import VFLConfig
    from repro.core.async_engine import EngineConfig
    from repro.data import lm_token_batches, vertical_partition
    from repro.federation import Federation

    cfg = lm_config(plan)
    lr = 0.01
    # the sphere estimator's norm grows like sqrt(d_client): scale the
    # client lr down by it, as the train driver does
    d_client = cfg.padded_vocab * cfg.d_model
    vfl = VFLConfig(mu=1e-3, lr_server=lr, lr_client=lr / math.sqrt(d_client),
                    zoo_queries=plan.zoo_queries)
    fed = Federation.build(
        cfg, vfl, EngineConfig(method="cascaded", steps=plan.async_rounds,
                               batch_size=plan.batch, use_lanes=True),
        n_clients=plan.clients, seq_len=plan.seq)
    params = fed.init_params(jax.random.key(0))
    toks = next(lm_token_batches(1, cfg.vocab_size, plan.async_rows,
                                 plan.seq))["tokens"]
    res = fed.run(params, jnp.asarray(vertical_partition(toks, plan.clients)),
                  jnp.asarray(toks))
    losses = np.asarray(res.losses)
    check(losses.shape == (plan.async_rounds,) and np.isfinite(losses).all(),
          f"async losses: {losses}")
    check(not res.transmits_gradients, "the async wire carried gradients")
    return {"losses": losses.tolist()}


def _tabular(plan: Plan):
    import jax
    import jax.numpy as jnp

    from repro.configs import VFLConfig
    from repro.configs.paper_mlp import PaperMLPConfig
    from repro.data import make_classification, vertical_partition
    from repro.models import common, tabular

    cfg = PaperMLPConfig(n_clients=plan.tabular_clients)
    X, y = make_classification(0, plan.tabular_rows, cfg.n_features,
                               cfg.n_classes)
    x_parts = jnp.asarray(vertical_partition(X, cfg.n_clients))
    params = common.materialize(tabular.param_specs(cfg), jax.random.key(0))
    vfl = VFLConfig(mu=1e-3, zoo_dist=TABULAR_ZOO_DIST, lr_server=0.05,
                    lr_client=0.05, zoo_queries=plan.zoo_queries)
    return cfg, vfl, params, x_parts, jnp.asarray(y)


def _rel(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def phase_kernel(plan: Plan) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import zoo
    from repro.core.adapters import tabular_adapter
    from repro.core.async_engine import EngineConfig
    from repro.federation import Federation

    cfg, vfl, params, x_parts, y = _tabular(plan)
    kernel_ad = tabular_adapter(cfg, use_pallas_lanes=True)
    xla_ad = tabular_adapter(cfg)
    out = {}
    with jax.default_matmul_precision(TABULAR_PRECISION):
        # the lanes themselves, on one client's batch
        client0 = jax.tree.map(lambda a: a[0], params["clients"])
        u_stack, _ = zoo.sample_directions(jax.random.key(1), client0,
                                           plan.zoo_queries, vfl.zoo_dist)
        xb = x_parts[0, :plan.tabular_batch]
        lanes = {name: np.asarray(jax.jit(ad.client_lanes)(
                     client0, u_stack, vfl.mu, xb))
                 for name, ad in (("kernel", kernel_ad), ("xla", xla_ad))}
        out["lanes_rel"] = _rel(lanes["kernel"], lanes["xla"])
        direction = {k: (v[1:] - v[:1]) / vfl.mu for k, v in lanes.items()}
        out["direction_rel"] = _rel(direction["kernel"], direction["xla"])
        check(out["lanes_rel"] <= LANES_RTOL,
              f"kernel lanes differ from XLA lanes: {out['lanes_rel']}")
        check(out["direction_rel"] <= DIRECTION_RTOL,
              f"kernel perturbations differ: {out['direction_rel']}")

        # the async engine end to end, once per lanes implementation
        engine = EngineConfig(method="cascaded", steps=plan.tabular_rounds,
                              batch_size=plan.tabular_batch, use_lanes=True)
        losses = {}
        for name, ad in (("kernel", kernel_ad), ("xla", xla_ad)):
            fed = Federation.build(ad, vfl, engine,
                                   n_clients=cfg.n_clients)
            res = fed.run(params, x_parts, y)
            losses[name] = np.asarray(res.losses)
            if name == "kernel":
                # the program the scan body runs, compiled as it ran
                step = fed.traceable_train_step()
                hlo = jax.jit(step).lower(
                    res.params, res.table, jnp.zeros((1,), jnp.int32),
                    jnp.zeros((plan.tabular_batch,), jnp.int32),
                    jax.random.key(0), x_parts, y).compile().as_text()
                check("tpu_custom_call" in hlo,
                      "the engine step holds no tpu_custom_call: the "
                      "Pallas kernel did not compile into it")
    for v in losses.values():
        check(np.isfinite(v).all(), f"tabular losses: {v}")
    out["max_loss_diff"] = float(np.max(np.abs(losses["kernel"]
                                               - losses["xla"])))
    check(out["max_loss_diff"] <= LOSS_ATOL,
          f"kernel and XLA engine losses differ by {out['max_loss_diff']}")
    return out


def phase_serve(plan: Plan) -> dict:
    import jax
    import numpy as np

    from repro.launch import serve as serve_mod

    res = serve_mod.serve(ARCH, batch=plan.requests,
                          prompt_len=plan.prompt_len, gen_len=plan.gen_len,
                          use_reduced=plan.use_reduced, n_layers=plan.layers,
                          n_clients=plan.clients, continuous=True,
                          max_batch=plan.slots, temperature=0.0)
    check(res["statuses"] == {"ok": plan.requests},
          f"not every request retired ok: {res['statuses']}")
    check(not res["wire_has_gradients"], "the serve wire carried gradients")
    # request 0 again, alone, through the solo decode path
    cfg = serve_mod.serve_config(ARCH, use_reduced=plan.use_reduced,
                                 n_layers=plan.layers)
    fed, key, params = serve_mod._build_session(
        cfg, n_clients=plan.clients, prompt_len=plan.prompt_len,
        gen_len=plan.gen_len, seed=0)
    prompts = serve_mod.request_prompts(key, 1, plan.prompt_len,
                                        cfg.vocab_size)
    solo = fed.decode(params, prompts, gen_len=plan.gen_len,
                      temperature=0.0, key=jax.random.fold_in(key, 0))
    solo_tokens = np.asarray(solo.tokens[0]).tolist()
    check(solo_tokens == res["tokens"][0],
          f"continuous request 0 {res['tokens'][0]} != solo decode "
          f"{solo_tokens}")
    return {"steps": res["steps"], "statuses": res["statuses"]}


def phase_four_chips(plan: Plan) -> dict:
    import jax
    import numpy as np

    from repro.core.adapters import tabular_adapter
    from repro.core.async_engine import EngineConfig
    from repro.federation import Federation

    cfg, vfl, params, x_parts, y = _tabular(plan)
    losses, out = {}, {}
    with jax.default_matmul_precision(TABULAR_PRECISION):
        for shards in (4, 1):
            engine = EngineConfig(method="cascaded",
                                  steps=plan.tabular_rounds,
                                  batch_size=plan.tabular_batch,
                                  block_size=plan.sharded_block,
                                  mesh_shards=shards)
            fed = Federation.build(tabular_adapter(cfg), vfl, engine,
                                   n_clients=cfg.n_clients)
            res = fed.run(params, x_parts, y)
            losses[shards] = np.asarray(res.losses)
            if shards == 4:
                placed = sorted((s.device.id, s.data.shape)
                                for s in res.table.addressable_shards)
                out["table_shards"] = [f"device {d}: {list(shape)}"
                                       for d, shape in placed]
                check(len({d for d, _ in placed}) == 4,
                      f"table shards are not on 4 devices: {placed}")
                check(all(shape[0] == cfg.n_clients // 4
                          for _, shape in placed),
                      f"table rows are not split 4 ways: {placed}")
    for v in losses.values():
        check(np.isfinite(v).all(), f"sharded losses: {v}")
    out["max_loss_diff"] = float(np.max(np.abs(losses[4] - losses[1])))
    check(out["max_loss_diff"] <= LOSS_ATOL,
          f"4-shard and 1-shard losses differ by {out['max_loss_diff']}")
    return out


# --------------------------------------------------------------- main ---

def run_phases(phases, device) -> None:
    import jax
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        for name, fn in phases:
            print(f"phase {name}: start", flush=True)
            c0, t0 = clock.seconds, time.perf_counter()
            detail = fn()
            wall = time.perf_counter() - t0
            stats = device.memory_stats() or {}
            print(f"phase {name}: ok (smoke figures, not benchmark numbers) "
                  f"wall_s={wall:.1f} backend_compile_s="
                  f"{clock.seconds - c0:.1f} peak_bytes_in_use="
                  f"{stats.get('peak_bytes_in_use', 'not reported')} "
                  f"{json.dumps(detail)}", flush=True)
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard client block vs 1 shard")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    check(d0.platform == "tpu", f"no TPU: JAX reports {d0.platform!r}")
    if args.four_chips:
        check(len(devices) >= 4, f"--four-chips needs 4 chips, found "
              f"{len(devices)}")

    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    plan = Plan()
    if args.four_chips:
        phases = [("four_chips", lambda: phase_four_chips(plan))]
    else:
        cfg = lm_config(plan)
        print(f"model: {ARCH} layers={cfg.n_layers} d_model={cfg.d_model} "
              f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} "
              f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype} "
              f"params={cfg.param_count()}", flush=True)
        phases = [("train", lambda: phase_train(plan)),
                  ("async", lambda: phase_async(plan)),
                  ("kernel", lambda: phase_kernel(plan)),
                  ("serve", lambda: phase_serve(plan))]
    run_phases(phases, d0)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
