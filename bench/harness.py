"""Shared plumbing of the benchmark: files found by name, seeds, the
device check, compile counting, weights from a seed, and the checks
that decide ``correct``.

Everything a cell needs is found by its name: ``workloads/<cell>.json``
names its configuration (``configs/<config>.json``) and its driver
(``drivers/<driver>.py``); ``flops/<config>.py`` counts the work the
algorithm needs; ``metrics/<metric>.py`` reads one per-layer metric. An
LM configuration's Hugging Face ``model_type`` names its architecture:
``arch/<model_type>.py`` maps the configuration to the program's model
(``model_config(cfg)``), and ``reference/<model_type>.py`` is its plain
reference (``items(cfg)``, ``hidden(cfg, dot, params, x)``), on which
``reference/lm.py`` builds the logits, the loss and the cascaded step.
The CPU tests find a cell's small sizes in ``tests/sizes/<cell>.py``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class BenchError(RuntimeError):
    """A run that must exit non-zero and print no result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------ discovery --

def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json at {ROOT}")
    return load_json(path)


def load_workload(name: str) -> dict:
    path = os.path.join(BENCH, "workloads", f"{name}.json")
    if not os.path.exists(path):
        raise BenchError(f"unknown workload {name!r}: no {path}")
    wl = load_json(path)
    wl["name"] = name
    return wl


def load_config(name: str) -> dict:
    path = os.path.join(BENCH, "configs", f"{name}.json")
    if not os.path.exists(path):
        raise BenchError(f"unknown config {name!r}: no {path}")
    cfg = load_json(path)
    cfg["name"] = name
    return cfg


def apply_overrides(wl: dict, cfg: dict, overrides: Optional[dict]) -> None:
    """Lay ``{"config": {...}, "workload": {...}}`` over a cell's files in
    place; a nested group of the config is merged key by key."""
    overrides = overrides or {}
    for k, v in overrides.get("config", {}).items():
        cfg[k] = ({**cfg[k], **v} if isinstance(v, dict)
                  and isinstance(cfg.get(k), dict) else v)
    wl.update(overrides.get("workload", {}))


def path_name(path) -> str:
    """``a/b/c``: a pytree key path as a name."""
    return "/".join(str(getattr(k, "key", k)) for k in path)


def leaf_names(tree) -> List[str]:
    """The path names of a pytree's leaves, in flattening order."""
    import jax
    return [path_name(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def load_module(kind: str, name: str):
    """``<bench>/<kind>/<name>.py`` as a module (names may hold dots),
    loaded once for each path."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"no {kind} module {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".translate(str.maketrans("./-", "___")), path)
    known = sys.modules.get(spec.name)
    if known is not None and known.__file__ == path:
        return known
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def model_type(cfg: dict) -> str:
    """An LM configuration's Hugging Face ``model_type``."""
    if "model_type" not in cfg:
        raise BenchError(f"config {cfg.get('name')!r} names no model_type")
    return cfg["model_type"]


def load_arch(cfg: dict):
    """``arch/<model_type>.py``: ``model_config(cfg)``, the program's
    model for an LM configuration."""
    return load_module("arch", model_type(cfg))


def load_reference(cfg: dict):
    """``reference/<model_type>.py``: the architecture's plain reference,
    ``items(cfg)`` and ``hidden(cfg, dot, params, x)``."""
    return load_module("reference", model_type(cfg))


def peaks() -> dict:
    return load_json(os.path.join(BENCH, "peaks.json"))["devices"]


def metrics_for(spec: dict, cell: str, section: str) -> List[dict]:
    """The entries of ``spec[section]`` that this cell reports: those
    that list it under ``workloads``, and those with no such list."""
    return [m for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------- seeds --

def seed32(seed: int, stream: int = 0) -> int:
    """A 31-bit seed drawn from any whole number (``jax.random.key``
    silently truncates seeds past 32 bits)."""
    import numpy as np
    ss = np.random.SeedSequence([int(seed) & ((1 << 128) - 1), stream])
    return int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)


# --------------------------------------------------------------- device --

def device_info(chips: int) -> dict:
    """JAX's devices, checked: a TPU whose kind is in ``peaks.json`` and
    at least ``chips`` of them. Anything else is a :class:`BenchError`."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise BenchError(f"no TPU: JAX reports platform {d0.platform!r}")
    if d0.device_kind not in peaks():
        raise BenchError(f"device kind {d0.device_kind!r} is not in "
                         "bench/peaks.json")
    if len(devices) < chips:
        raise BenchError(f"cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax
    peaks_seen = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_seen.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_seen) if peaks_seen else None


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``<checkout>/.jax_cache`` (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program written to it."""
    import jax
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts the programs this process compiled or loaded from the
    persistent cache while it listens (``count``), and the seconds of
    backend compilation (``seconds``)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.count += 1

    def __enter__(self) -> "CompileClock":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self)
        jax.monitoring.unregister_event_listener(self._event)


# -------------------------------------------------------------- weights --

class Weights:
    """Weights for a spec tree from a seed, made on the device in each
    leaf's own dtype: the whole tree in one jitted call, or one leaf at a
    time (bitwise the same values) to read a leaf's change from its start
    without keeping a copy of the start.

    Leaves whose spec says ``ones``/``zeros`` get that; every other leaf
    is drawn from N(0, std²), where ``rule`` gives std: ``{"std": s}``
    for a fixed one, or ``{"fan_in": true}`` for 1/sqrt(shape[-2]).
    ``zero_rows`` maps a leaf's path (``lm_head/table``) to the number of
    its leading rows that are drawn; the rows past them are 0 (a
    vocabulary padded past the published one)."""

    def __init__(self, spec_tree, rule: dict, is_leaf: Callable,
                 zero_rows: Optional[Dict[str, int]] = None) -> None:
        import jax
        import jax.numpy as jnp
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(
            spec_tree, is_leaf=is_leaf)
        self.names = [path_name(p) for p, _ in flat]
        self.metas = [(tuple(s.shape), str(jnp.dtype(s.dtype)), s.init)
                      for _, s in flat]
        self.rule = rule
        self.zero_rows = zero_rows or {}
        self._all = jax.jit(self._make_all)
        self._gap = jax.jit(self._leaf_gap_norm, static_argnums=(1,))

    def _std(self, shape) -> float:
        if self.rule.get("fan_in"):
            return 1.0 / math.sqrt(shape[-2] if len(shape) >= 2 else 1)
        return float(self.rule["std"])

    def _make_leaf(self, key, i: int):
        import jax
        import jax.numpy as jnp
        import numpy as np
        shape, dt, init = self.metas[i]
        if init == "ones":
            return jnp.ones(shape, dt)
        if init == "zeros":
            return jnp.zeros(shape, dt)
        k = jax.random.fold_in(key, i)
        w = jax.random.normal(k, shape, jnp.float32) * np.float32(
            self._std(shape))
        rows = self.zero_rows.get(self.names[i])
        if rows is not None and rows < shape[0]:
            keep = jnp.arange(shape[0]) < rows
            w = w * keep.reshape((-1,) + (1,) * (len(shape) - 1))
        return w.astype(dt)

    def _make_all(self, key):
        import jax
        return jax.tree.unflatten(
            self.treedef, [self._make_leaf(key, i)
                           for i in range(len(self.metas))])

    def _leaf_gap_norm(self, key, i: int, x):
        import jax.numpy as jnp
        d = (self._make_leaf(key, i).astype(jnp.float32)
             - x.astype(jnp.float32))
        return jnp.sqrt(jnp.sum(jnp.square(d)))

    @staticmethod
    def key(seed: int):
        import jax
        return jax.random.key(seed32(seed, 1))

    def all(self, seed: int):
        return self._all(self.key(seed))

    def change_norms(self, seed: int, tree) -> List[float]:
        """Per leaf, the norm of (leaf as made from ``seed``) - (leaf of
        ``tree``), one leaf made at a time."""
        import jax
        key = self.key(seed)
        return [float(self._gap(key, i, x))
                for i, x in enumerate(jax.tree.leaves(tree))]


# --------------------------------------------------------------- checks --

@dataclasses.dataclass
class Check:
    """One number compared with its limit; ``ok`` when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             counted: List[str]) -> float:
    """The worst leaf's gap between two per-leaf norms, each against the
    larger of the reference's norm of that leaf and of the median leaf."""
    import numpy as np
    med = float(np.median([ref[k] for k in counted]))
    worst = 0.0
    for k in counted:
        den = max(ref[k], med)
        gap = abs(prog[k] - ref[k]) / den if den > 0 else 0.0
        worst = max(worst, gap if math.isfinite(gap) else math.inf)
        if not math.isfinite(prog[k]):
            worst = math.inf
    return worst


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              counted: List[str]) -> Dict[str, float]:
    """Each counted leaf's gap, as :func:`leaf_gap` takes its worst."""
    import numpy as np
    med = float(np.median([ref[k] for k in counted]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med)
            if max(ref[k], med) else 0.0 for k in counted}


def median_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                    counted: List[str]) -> float:
    """The median over leaves of the same per-leaf gap as
    :func:`leaf_gap`: steady where one leaf's number is noise."""
    import numpy as np
    med = float(np.median([ref[k] for k in counted]))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med) if max(ref[k], med)
            else 0.0 for k in counted]
    return float(np.median(gaps))


def counted_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's."""
    import numpy as np
    med = float(np.median(list(ref_grad.values())))
    return [k for k, v in ref_grad.items() if v >= 1e-3 * med]


def rel_gap(a: float, b: float) -> float:
    if not math.isfinite(a):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-30)


# ---------------------------------------------------------------- clock --

class Window:
    """The measured window: a fixed length of host time."""

    def __init__(self, seconds: float) -> None:
        self.seconds = float(seconds)
        self.t0 = time.perf_counter()
        self.t1: Optional[float] = None

    def open(self) -> bool:
        return time.perf_counter() - self.t0 < self.seconds

    def close(self) -> float:
        self.t1 = time.perf_counter()
        return self.t1 - self.t0
