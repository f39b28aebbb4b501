"""Compile the single-device async runner and the sync cascaded step,
and read their optimized HLO.

The async runner is shared by the CPU guard in ``test_async_sharded.py``
and the described-v5e guard in ``test_tpu_compile.py``: both assert that
the scanned round updates the server's embedding table in place, with no
copy of the whole table inside the loop. The cascaded step is compiled
for a described v5e in ``test_tpu_compile.py``: it must fit the chip and
backpropagate the server through the clean lane alone."""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(")
_CALLEE = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations)=\{?%?([\w.\-]+)")
_INSTR = re.compile(r"(?:ROOT )?%?[\w.\-]+ = (\S+?)\{[^ ]* ([\w\-]+)\(")


def loop_copies(hlo: str, shapes) -> list:
    """Instructions of every ``while`` body, and of what it calls, that
    copy or transpose into one of ``shapes`` (HLO spellings such as
    ``"f32[4,600,128]"``)."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line.strip())
    todo = [b for lines in comps.values() for line in lines
            if re.search(r" while\(", line)
            for b in re.findall(r"body=%?([\w.\-]+)", line)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += _CALLEE.findall("\n".join(comps[name]))
    return [line for name in seen for line in comps[name]
            if (m := _INSTR.match(line)) and m.group(1) in shapes
            and m.group(2) in ("copy", "copy-start", "transpose")]


def async_runner_hlo(*, n: int, block: int, use_lanes: bool = True,
                     steps: int = 64, sharding=None) -> str:
    """Optimized HLO of the async runner for the paper's tabular job
    (M = 4 parties, 196 features and 128 embeddings each, batch 256)
    over ``n`` rows, compiled from shapes alone (``sharding`` places
    them on a described device)."""
    from repro.configs import VFLConfig
    from repro.configs.paper_mlp import PaperMLPConfig
    from repro.core import async_engine
    from repro.core.adapters import tabular_adapter
    from repro.federation import Federation
    from repro.models import common, tabular

    cfg = PaperMLPConfig(n_features=784, n_classes=10, n_clients=4,
                         client_embed=128, server_embed=128)
    M, f, e, bs = cfg.n_clients, cfg.features_per_client, \
        cfg.client_embed, 256
    vfl = VFLConfig(mu=1e-3, zoo_dist="sphere", zoo_queries=1)
    engine = async_engine.EngineConfig(method="cascaded", steps=steps,
                                       batch_size=bs, block_size=block,
                                       use_lanes=use_lanes)
    fed = Federation.build(tabular_adapter(cfg), vfl, engine, n_clients=M)
    runner = async_engine._make_runner(fed.adapter, fed.transport, vfl,
                                       False, block, use_lanes)
    S = jax.ShapeDtypeStruct
    args = [common.abstract(tabular.param_specs(cfg)),
            S((M, n, e), jnp.float32), S((M, n), jnp.int32),
            S((steps, block), jnp.int32), S((steps, bs), jnp.int32),
            jax.eval_shape(lambda: jax.random.split(jax.random.key(0),
                                                    steps)),
            S((M, n, f), jnp.float32), S((n,), jnp.int32)]
    if sharding is not None:
        args = jax.tree.map(lambda s: S(s.shape, s.dtype, sharding=sharding),
                            args)
    with jax.default_matmul_precision("highest"):
        return runner.lower(*args).compile().as_text()


def cascaded_step_compiled(sharding, *, n_layers: int = 2, batch: int = 8,
                           seq: int = 512, q: int = 4):
    """The sync cascaded step of ``Federation.sync_step`` at phi3-mini's
    published widths over ``n_layers`` layers, jitted as the train driver
    jits it (``donate_argnums=(0, 1)``), SGD, compiled from shapes placed
    by ``sharding``."""
    from repro.configs import VFLConfig, driver_config
    from repro.core.async_engine import EngineConfig
    from repro.federation import Federation
    from repro.models import common
    from repro.optim import make_schedule, sgd

    cfg = driver_config("phi3-mini-3.8b", use_reduced=False,
                        n_layers=n_layers)
    assert (cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab_size) == (
        3072, 32, 8192, 32064)
    fed = Federation.build(cfg, VFLConfig(zoo_queries=q),
                           EngineConfig(method="cascaded", batch_size=batch),
                           seq_len=seq)
    opt = sgd(make_schedule("constant", 0.01))

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    params = jax.tree.map(place, common.abstract(fed.model.param_specs))
    opt_state = jax.tree.map(place, jax.eval_shape(opt.init, params))
    tokens = place(jax.ShapeDtypeStruct((batch, seq), jnp.int32))
    key = place(jax.eval_shape(lambda: jax.random.key(0)))
    return jax.jit(fed.sync_step(opt), donate_argnums=(0, 1)).lower(
        params, opt_state, {"tokens": tokens, "labels": tokens},
        key).compile()
