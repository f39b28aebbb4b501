"""Compile the single-device async runner and read its optimized HLO.

Shared by the CPU guard in ``test_async_sharded.py`` and the described-v5e
guard in ``test_tpu_compile.py``: both assert that the scanned round
updates the server's embedding table in place, with no copy of the
whole table inside the loop."""
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
