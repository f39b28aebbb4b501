"""Continuous batching for the split serve plane, on paged caches.

The sglang-style serving loop, with the VFL party split kept intact: a
:class:`ServeScheduler` owns ``max_batch`` fixed SLOTS whose
sequence-indexed cache state lives in a shared page pool
(:mod:`repro.federation.paging`) addressed through per-slot block
tables, admits queued requests into free slots mid-flight, and drives
the whole churning mix with compiled MULTI-STEP decode blocks.

The first scheduler revision lost 6.6× to the static batched path by
doing host work per token: a Python dispatch per step, a per-active-slot
ledger call per token, and a blocking device→host fetch inside
``_retire``. This revision keeps the host out of the loop:

* **block stepping** — ``remaining`` lives on device and derives the
  active mask, so a compiled ``lax.scan`` block of K steps needs no host
  intervention. K is the largest power of two that no active request
  outlives (``K <= min(remaining)``), so a block never overshoots a
  retirement, the compiled-block set is bounded by ``log2(seq_len)``
  programs, and an occupied slot is never stepped while logically done.
* **wave retirement** — after a block, every slot whose host-mirrored
  ``remaining`` hit zero retires together: ONE batched device→host fetch
  per wave (``host_transfers`` counts them — O(requests), not O(steps)).
* **deferred accounting** — prefill wire traffic is logged at admission
  (``n_steps=prompt_len, n_gen=0``) and generation at retirement
  (``n_steps=gen_len, n_gen=gen_len``). ``Transport.account_serve``
  appends ``serve_messages(b, e, with_token=False) * (n_steps - n_gen)``
  then ``serve_messages(b, e) * n_gen``, so admission + retirement
  produce exactly ``up×prompt_len`` then ``(up+token)×gen_len`` — the
  byte-identical Message list a solo ``fed.decode`` logs in its single
  ``account_serve(n_steps=prompt_len+gen_len, n_gen=gen_len)`` call, and
  what the per-step ``account_serve_step`` metering used to build one
  token at a time.
* **wave admission** — the queue's head run of equal-length prompts is
  admitted as ONE wave: one batched chunk-prefill and one compiled
  install scatter cover the whole wave (width-1 waves reuse a persistent
  dense ``(1, seq_len)`` buffer — only the small recurrent state leaves
  are re-zeroed; stale KV rows beyond the prompt are masked exactly).
  Admission issues only async dispatches — no host sync, and admission
  is page-gated FIFO: an undersized pool makes requests wait for pages,
  never reorder.

Sampling uses the same ``fold_in(request_key, 100 + t)`` stream as the
solo path, so a request's tokens do not depend on what shared the batch.

**Failure policy** (the robustness layer):

* **bounded queue** — ``max_queue`` turns unbounded FIFO growth into
  typed backpressure: ``submit`` past the bound raises
  :class:`QueueFull` instead of silently deepening the backlog.
* **deadlines** — ``submit(deadline=D)`` gives the request D scheduler
  steps to RETIRE. An admitted request always meets its deadline (every
  block steps every occupied slot), so misses happen in the queue: the
  admission loop expires any queued request that can no longer finish in
  time (``status="deadline"``, partial tokens, ledger metering exactly
  what ran).
* **cancellation** — :meth:`cancel` removes a queued request or evicts
  an in-flight one between blocks (``status="cancelled"``); its ledger
  meters exactly the steps it ran — admission's prompt uploads plus one
  generation entry per token actually produced, byte-identical to a solo
  decode truncated at the same length.
* **preemption** — when the queue's head cannot get pages while a slot
  is free, the scheduler may evict a victim (fewest tokens remaining
  wins; only slots that progressed since admission are eligible, which
  makes the policy livelock-free) and re-queue it. On re-admission the
  victim re-prefills its prompt, REPLAYS its already-generated tokens
  through the per-token serve step, and resumes at the same absolute
  position ``t`` — the sampling stream is ``fold_in(key, 100 + t)``, so
  the resumed tokens are BITWISE what the unpreempted run would have
  produced (pinned by tests next to the continuous==solo guarantee).
  Preemption overhead is metered honestly: the evicted tenancy's
  generation entries at eviction, the full re-prefill (prompt +
  generated-so-far uploads) at re-admission.
* **poison isolation** — a request whose logits go non-finite fails with
  ``status="poisoned"`` at its next host-fetch point (retirement or
  eviction), never the engine: its pages are scrubbed to zero before
  reuse, because NaN — unlike the usual stale bytes — survives the
  causal mask (``0·NaN = NaN``) and would leak into the page's next
  tenant.
* **durability** — :meth:`snapshot` captures the whole serve plane
  (queue, slot tables, page-pool free list order, gen buffers,
  per-request ledgers, RNG key streams) as a :class:`SchedulerState`
  that saves through ``fed.save(serve_state=...)``; a scheduler restored
  mid-drain (``run(max_steps=...)`` then kill) continues bitwise — same
  token streams, byte-identical per-request ledgers — mirroring the
  async training plane's ``AsyncPlaneState`` contract.

**Spans** (:mod:`repro.utils.spans`, kept while a profiler session
records): ``vfl.sched.run`` around :meth:`ServeScheduler.run`; inside it
``vfl.sched.admit`` per admitted wave (``rids``), holding
``vfl.sched.prefill_wave`` (``width``, ``prompt_len``) and
``vfl.sched.install``; ``vfl.sched.block`` per decode block (``k``,
``occupancy``); ``vfl.sched.retire`` per retirement wave, holding
``vfl.sched.retire_fetch``, its blocking read. Two kinds of interval are
recorded after the fact: ``vfl.sched.queued`` (``rid``), a request's wait
from joining the queue to admission; and ``vfl.sched.drained``
(``after=entry|retire|evict``), a stretch in which the scheduler's host
code runs while none of its compiled programs (prefill chunk, replay
step, install, decode block) is queued on the device — from ``run``'s
entry or the return of a blocking read to the next such dispatch or
``run``'s exit. Small eager updates and the block-table upload count as
host work. Where the expert layers hold a share of the experts, a
zero-length ``vfl.sched.moe`` record at ``run``'s entry and exit holds
:meth:`ServeScheduler.moe_counters` (device scalars, unread).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import tags
from repro.checkpoint.io import load_tree, save_checkpoint
from repro.core.adapters import ModelAdapter
from repro.core.privacy import Ledger, Message
from repro.federation import paging, serving
from repro.utils import spans


class QueueFull(RuntimeError):
    """Typed backpressure: the admission queue is at ``max_queue`` — shed
    load upstream instead of queueing unboundedly."""


@dataclasses.dataclass
class ServeRequest:
    """A queued generation request (one sequence; batch=1 on the wire)."""
    rid: int
    prompt: np.ndarray              # (prompt_len,) int32
    gen_len: int
    key: jax.Array                  # typed PRNG key — solo-compatible stream
    ledger: Ledger = dataclasses.field(default_factory=Ledger)
    deadline: Optional[int] = None  # absolute scheduler step to retire by
    # tokens generated before a preemption (replayed at re-admission)
    generated: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int32))
    preemptions: int = 0
    first_admitted: int = -1        # -1 = never admitted
    # when the request last joined the queue (``time.time_ns``; submit,
    # preemption or restore): the start of its ``vfl.sched.queued``
    # interval. Kept out of snapshots and ledgers.
    queued_ns: int = dataclasses.field(default_factory=time.time_ns,
                                       compare=False, repr=False)


@dataclasses.dataclass
class RequestResult:
    """One drained request: its tokens and its exact wire ledger.

    ``status`` is ``"ok"`` for a full retirement; ``"cancelled"`` /
    ``"deadline"`` / ``"poisoned"`` results carry the tokens generated up
    to the failure and a ledger metering exactly the steps that ran."""
    rid: int
    tokens: np.ndarray              # (gen_len,) sampled token ids
    ledger: Ledger
    prompt_len: int
    admitted_at: int                # scheduler step index at admission
    finished_at: int                # scheduler step index at retirement
    status: str = "ok"
    preemptions: int = 0

    @property
    def wire_bytes(self) -> int:
        return self.ledger.total_bytes

    @property
    def transmits_gradients(self) -> bool:
        return self.ledger.transmits_gradients


# -------------------------------------------------- ledger (de)serialize --
# SchedulerState needs per-request ledgers BYTE-identical across a
# save/restore, including message ORDER — Ledger.to_counts aggregates
# (fine for totals, lossy for interleavings), so the serve plane keeps
# its own exact row codec.

def _ledger_rows(led: Ledger) -> List[list]:
    return [[m.sender, m.kind, list(m.shape), m.dtype, m.wired]
            for m in led.messages]


def _ledger_from_rows(rows: List[list]) -> Ledger:
    led = Ledger()
    led.messages.extend(
        Message(sender, kind, tuple(shape), dtype,
                wired=None if wired is None else int(wired))
        for sender, kind, shape, dtype, wired in rows)
    return led


@dataclasses.dataclass
class SchedulerState:
    """A complete serve-plane snapshot: every device buffer (page pool,
    slot state, gen buffers, RNG key data), the host bookkeeping (queue,
    slot tables, allocator free-list ORDER, per-request ledgers, result
    set, counters) and the constructor config. ``fed.save(serve_state=)``
    persists it; ``fed.serve(params, state=...)`` resumes it bitwise."""
    flat: Dict[str, np.ndarray]     # array leaves, keystr-addressed
    meta: dict                      # JSON-able bookkeeping + config

    def save(self, path: str) -> str:
        save_checkpoint(path, self.flat, metadata=self.meta)
        return path

    @classmethod
    def load(cls, path: str) -> "SchedulerState":
        tree, _, meta = load_tree(path)
        return cls(flat={k: np.asarray(v) for k, v in tree.items()},
                   meta=meta)


def _leafkey(group: str, path: Any) -> str:
    # "x" prefix keeps load_tree's dict-only key grammar happy (keystr
    # output starts with "[")
    return f"x['{group}']" + jax.tree_util.keystr(path)


@functools.lru_cache(maxsize=64)
def make_paged_decode_block(adapter: ModelAdapter, n_clients: int,
                            seq_len: int, temperature: float,
                            vocab_size: int, page_size: int,
                            n_slots: int, n_steps: int):
    """A compiled block of ``n_steps`` continuous-batching decode steps.

    Per step every slot samples from its carried logits on its own key
    stream, the owning client embeds the token, and the server runs ONE
    batched paged decode over all slots (``server_decode_paged``). The
    active mask derives on device from ``remaining > 0``, so the host
    never touches the loop; a slot that hits zero simply freezes (its
    uplink embedding is zeroed, its recurrent state held, its KV row
    routed to the trash page).

    Inactive slots still pay the backbone FLOPs for their batch row:
    under a batched (or vmapped) step XLA lowers per-row ``cond`` to
    ``select`` — both branches run — and a dense matmul has no ragged
    batch. The engine bounds that waste structurally instead: the block
    length never exceeds the smallest active ``remaining`` (an occupied
    slot is never stepped past its retirement) and the host loop stops
    the moment no slot is occupied, so idle rows only ride along while
    the queue is empty and other slots still stream. True row skipping
    needs slot compaction across bucketed batch sizes (a recompile per
    occupancy) or ragged kernels — a TPU-pass item (see ROADMAP).
    """
    serving._require_serve_plane(adapter)
    if adapter.server_decode_paged is None:
        raise ValueError(
            f"adapter {adapter.name!r} has no server_decode_paged hook; "
            "the paged continuous scheduler needs it")
    span = seq_len // n_clients

    def block(params, tables, keydata_st, logits_st, caches_st, t_st,
              gen_pos_st, rem_st, gen_buf_st):
        sl = jnp.arange(n_slots)

        @tags.wire("up", accounted_by="Transport.account_serve",
                   kind="embedding",
                   reason="continuous-batching decode step: each active "
                          "slot's client embeds its sampled token and the "
                          "embedding crosses to server_decode_paged; the "
                          "traffic is metered deferred — prompt uploads at "
                          "admission, generation at retirement (see module "
                          "docstring)")
        def body(carry, _):
            logits, caches, t, gen_pos, rem, gen_buf = carry
            active = (rem > 0).astype(jnp.int32)
            with jax.named_scope("serve.sample"):
                nxt = jax.vmap(
                    lambda lg, kd, tt: serving.sample_token(
                        lg, jax.random.wrap_key_data(kd), tt, temperature,
                        vocab_size))(logits, keydata_st, t)    # (n, 1)
                nxt = nxt[:, 0]
                idx = jnp.clip(gen_pos, 0, gen_buf.shape[1] - 1)
                gen_buf = gen_buf.at[sl, idx].set(
                    jnp.where(active > 0, nxt, gen_buf[sl, idx]))

            m = jnp.where(active > 0, t, 0) // span

            def embed_one(tok, mi):
                client_m = jax.tree.map(lambda a: a[mi], params["clients"])
                return adapter.client_embed(client_m, tok[None, None])

            with jax.named_scope("serve.client_embed"):
                e = jax.vmap(embed_one)(nxt, m)[:, 0]          # (n, 1, d)
                e = e * (active > 0).astype(e.dtype)[:, None, None]
            with jax.named_scope("serve.server_decode"):
                lg, caches, stats = adapter.server_decode_paged(
                    params["server"], e, caches, tables, t, active,
                    page_size)
            # the active rows' pairs routed to held experts (none where
            # the expert layers hold every expert: stats is empty)
            pairs = {k: jnp.sum(v * active[None, :, None])
                     for k, v in stats.items()}
            return (lg[:, None], caches, t + active, gen_pos + active,
                    rem - active, gen_buf), pairs

        carry, pairs = jax.lax.scan(
            body, (logits_st, caches_st, t_st, gen_pos_st, rem_st,
                   gen_buf_st), None, length=n_steps)
        return carry + (jax.tree.map(jnp.sum, pairs),)

    return jax.jit(block, donate_argnums=(3, 4, 5, 6, 7, 8))


@jax.jit
def _add_wave_load(acc, load, real):
    """``acc`` plus one prefill chunk's held-expert load (``moe_load`` of
    the backbone's stats, (layers, rows, held)) over its real rows."""
    per = jnp.sum(load * real[None, :, None], axis=1)     # (layers, held)
    return {"pairs": acc["pairs"] + jnp.sum(per),
            "wave_load_max": acc["wave_load_max"]
            + jnp.sum(jnp.max(per, axis=-1)),
            "wave_load_sum": acc["wave_load_sum"] + jnp.sum(per)}


@jax.jit
def _add_decode_pairs(acc, pairs):
    return dict(acc, pairs=acc["pairs"] + pairs)


@functools.lru_cache(maxsize=32)
def make_install_prog(adapter: ModelAdapter, seq_len: int):
    """The slot-install scatter: move a wave of freshly prefilled
    requests from the dense prefill buffer into their allocated pages
    (pooled leaves) / their slot rows (state leaves), and set the wave's
    logits, clocks, remaining counters, gen buffers and key streams in
    one compiled call. One program per (prompt_len, wave_width) shape
    pair; shared across scheduler instances (lru on the frozen adapter).

    ``gen_rows``/``gen_pos0s`` seed the generation buffer — zeros for a
    fresh request, the already-generated prefix (with its length as the
    write cursor) for a preempted request being resumed."""
    plans = paging.leaf_plans(adapter.cache_specs(1, seq_len))

    def install(caches_st, logits_st, t_st, gen_pos_st, rem_st,
                keydata_st, gen_buf_st, dense_caches, logits, rows, slots,
                t0s, rem0s, keydata_w, gen_rows, gen_pos0s):
        w = slots.shape[0]      # prefill may pad the wave past w rows

        def one(st, dense, plan):
            if plan.pooled:
                # pooled leaves are (layers, B, S, *tail) densely: scatter
                # each wave row's first prompt_len positions to its pages
                n_pages, pg = st.shape[1], st.shape[2]
                flat = st.reshape((st.shape[0], n_pages * pg)
                                  + st.shape[3:])
                vals = dense[:, :w, :rows.shape[1]]
                flat = flat.at[:, rows].set(vals.astype(st.dtype))
                return flat.reshape(st.shape)
            idx = (slice(None),) * plan.batch_axis + (slots,)
            dense = jax.lax.slice_in_dim(dense, 0, w, axis=plan.batch_axis)
            return st.at[idx].set(dense.astype(st.dtype))

        caches_st = jax.tree.map(one, caches_st, dense_caches, plans)
        return (caches_st, logits_st.at[slots].set(logits[:w, None]),
                t_st.at[slots].set(t0s),
                gen_pos_st.at[slots].set(gen_pos0s),
                rem_st.at[slots].set(rem0s),
                keydata_st.at[slots].set(keydata_w),
                gen_buf_st.at[slots].set(gen_rows))

    return jax.jit(install, donate_argnums=(0, 1, 2, 3, 4, 5, 6))


class ServeScheduler:
    """Continuous-batching engine over the split serve plane.

    ``submit()`` queues requests; ``run()`` drains the queue through the
    fixed slots and returns :class:`RequestResult` per request (rid
    order). Construct via :meth:`repro.federation.Federation.serve`.

    ``page_size`` must divide ``seq_len`` (default: the largest divisor
    <= 8); ``n_pages`` sizes the shared pool (default: worst case,
    ``max_batch`` full-length sequences + the two reserved pages). A
    smaller pool admission-gates requests on free pages instead of free
    slots — peak cache memory then tracks the lengths actually in
    flight, not ``max_batch × seq_len``. With ``preempt=True`` a
    page-starved queue head may instead evict the in-flight request with
    the fewest tokens remaining (bitwise-exact resume; see the module
    docstring). ``max_queue`` bounds the admission queue (``submit``
    raises :class:`QueueFull` past it).
    """

    def __init__(self, adapter: ModelAdapter, transport, *, params,
                 n_clients: int, seq_len: int, embed_dim: int,
                 vocab_size: int, max_batch: int = 4,
                 temperature: float = 0.0,
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 preempt: bool = False):
        serving._require_serve_plane(adapter)
        if adapter.server_decode_paged is None:
            raise ValueError(
                f"adapter {adapter.name!r} has no server_decode_paged "
                "hook; build the session from a ModelConfig to serve")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.adapter = adapter
        self.transport = transport
        self.params = params
        self.n_clients = n_clients
        self.seq_len = seq_len
        self.span = seq_len // n_clients
        self.embed_dim = embed_dim
        self.vocab_size = vocab_size
        self.max_batch = max_batch
        # device slot rows: max_batch, padded to the serve plane's floor
        # (rows past max_batch are never occupied and stay inactive)
        self.n_rows = max(max_batch, serving.MIN_ROWS)
        self.temperature = float(temperature)
        self.max_queue = max_queue
        self.preempt = bool(preempt)

        self.page_size = (paging.default_page_size(seq_len)
                          if page_size is None else int(page_size))
        if self.page_size < 1 or seq_len % self.page_size:
            raise ValueError(
                f"page_size={self.page_size} must divide seq_len={seq_len}")
        self.pages_per_seq = seq_len // self.page_size
        self.n_pages = (max_batch * self.pages_per_seq + paging.N_RESERVED
                        if n_pages is None else int(n_pages))
        self.allocator = paging.PageAllocator(self.n_pages)

        self._queue: List[ServeRequest] = []
        self._next_rid = 0
        self._slot_req: List[Optional[ServeRequest]] = [None] * max_batch
        self._slot_pages: List[Optional[np.ndarray]] = [None] * max_batch
        self._remaining = np.zeros(max_batch, np.int64)   # host mirror
        self._admitted_at = np.zeros(max_batch, np.int64)
        self._tables = np.full((self.n_rows, self.pages_per_seq),
                               paging.ZERO_PAGE, np.int32)
        self._tables_dev = None     # device mirror, rebuilt on mutation
        self._results: Dict[int, RequestResult] = {}

        # device-side slot state. Sequence cache leaves live in the shared
        # page pool; recurrent state leaves are slot-stacked. (Logits
        # dtype is model-dependent — built lazily from the first prefill.)
        dense_specs = adapter.cache_specs(1, seq_len)
        self._plans = paging.leaf_plans(dense_specs)
        paged_specs = paging.paged_specs(
            dense_specs, n_slots=self.n_rows, n_pages=self.n_pages,
            page_size=self.page_size)
        self._caches_st = jax.tree.map(
            lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)), paged_specs,
            is_leaf=lambda x: hasattr(x, "logical"))
        self._logits_st = None      # (slots, 1, 1, vocab)
        self._t_st = jnp.zeros(self.n_rows, jnp.int32)
        self._gen_pos_st = jnp.zeros(self.n_rows, jnp.int32)
        self._rem_st = jnp.zeros(self.n_rows, jnp.int32)
        self._gen_buf_st = jnp.zeros((self.n_rows, seq_len), jnp.int32)
        kd = jax.random.key_data(jax.random.key(0))
        self._keydata_st = jnp.zeros((self.n_rows,) + kd.shape, kd.dtype)

        # persistent dense (MIN_ROWS, seq_len) prefill buffer for width-1
        # waves (the request's row and copies of it) — only its small
        # recurrent-state leaves are re-zeroed per admission
        self._prefill_caches = None
        # hot-loop executables keyed on the block length — the
        # steady-state path never rebuilds an AOT cache key per block
        self._block_progs: Dict[int, object] = {}

        # perf + failure counters (the throughput/chaos benches read these)
        self.steps = 0
        self.compile_s = 0.0
        self.generated_tokens = 0
        self.last_run_s = 0.0
        self.host_transfers = 0     # device->host fetches (one per wave)
        self.preemptions = 0
        self.deadline_misses = 0
        self.poisoned = 0
        self.admitted = 0           # admissions (a resumed victim's too)
        self.prefill_waves = 0
        # expert layers that hold a share of the experts (see
        # moe_counters); None until a prefill shows such layers
        self.expert_tokens = 0
        self._moe_acc: Optional[Dict[str, jax.Array]] = None
        self._moe_shape = (0, 0)    # (expert layers, held experts)
        # (since, after) while none of the scheduler's programs is queued
        # on the device; closed into a vfl.sched.drained span at dispatch
        self._drained: Optional[tuple] = None

    # ------------------------------------------------------- queueing ----
    def submit(self, prompt, gen_len: int, *, seed: Optional[int] = None,
               key=None, deadline: Optional[int] = None) -> int:
        """Queue one request; returns its rid. ``key`` (or ``seed``) is
        the request's sampling stream — the SAME key given to a solo
        ``fed.decode`` yields the same tokens. Without either, each
        request gets its own stream (folded from its rid), so concurrent
        sampled requests are never correlated. ``deadline`` gives the
        request that many SCHEDULER STEPS (from now) to retire; raises
        :class:`QueueFull` when the admission queue is at ``max_queue``."""
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            raise QueueFull(
                f"admission queue full ({len(self._queue)}/"
                f"{self.max_queue}); retry after a drain")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1 or gen_len < 1:
            raise ValueError(
                f"need a non-empty prompt and gen_len >= 1, got "
                f"prompt_len={prompt.size}, gen_len={gen_len}")
        if prompt.size + gen_len > self.seq_len:
            raise ValueError(
                f"prompt_len + gen_len = {prompt.size + gen_len} exceeds "
                f"the session seq_len {self.seq_len}")
        need = paging.pages_needed(prompt.size + gen_len, self.page_size)
        if need > self.allocator.capacity:
            raise ValueError(
                f"request needs {need} pages but the pool holds "
                f"{self.allocator.capacity} (n_pages={self.n_pages}, "
                f"page_size={self.page_size})")
        if deadline is not None and deadline < 1:
            raise ValueError(f"deadline must be >= 1 steps, got {deadline}")
        rid = self._next_rid
        if key is None and seed is None:
            key = jax.random.fold_in(jax.random.key(0), rid)
        elif key is None:
            key = jax.random.key(seed)
        self._next_rid += 1
        self._queue.append(ServeRequest(
            rid=rid, prompt=prompt, gen_len=gen_len, key=key,
            deadline=None if deadline is None else self.steps + deadline))
        return rid

    def cancel(self, rid: int) -> Optional[RequestResult]:
        """Explicitly cancel a request. Queued: removed outright.
        In-flight: evicted between blocks — its tokens so far come back
        and its ledger meters exactly the steps it ran. Returns the
        terminal ``status="cancelled"`` result, or None if ``rid`` is
        unknown or already finished."""
        if rid in self._results:
            return None
        for i, req in enumerate(self._queue):
            if req.rid == rid:
                self._queue.pop(i)
                return self._fail_request(req, "cancelled")
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.rid == rid:
                return self._evict_slot(slot, "cancelled")
        return None

    # ------------------------------------------------------ admission ----
    def _prefill_wave(self, reqs: List[ServeRequest]):
        """Chunk-prefill a wave of equal-length prompts as ONE batch.

        A width-1 wave reuses the persistent dense buffer (recurrent
        state leaves re-zeroed; stale KV rows from the previous tenant
        sit beyond the causal mask of every prefill query position and
        contribute exactly 0.0 — bitwise-identical to a fresh zero
        buffer). Wider waves prefill through one (w, prompt_len) batch
        into transient zero caches: w prompts pay ONE dispatch chain
        instead of w. Batched rows staying bitwise-equal to a B=1
        prefill is an empirical backend property, not an XLA guarantee —
        exactly the same status as the decode scan matching the eager
        loop or split matching global — and it is pinned by
        tests/test_serving_engine.py (wave admission at sampling
        temperature, where low-bit drift is visible)."""
        w = len(reqs)
        rows = max(w, serving.MIN_ROWS)
        prompt_len = reqs[0].prompt.size
        if w == 1:
            if self._prefill_caches is None:
                self._prefill_caches = serving.zero_caches(
                    self.adapter, rows, self.seq_len)
            else:
                self._prefill_caches = jax.tree.map(
                    lambda a, plan: a if plan.pooled else jnp.zeros_like(a),
                    self._prefill_caches, self._plans)
            caches = self._prefill_caches
        else:
            caches = serving.zero_caches(self.adapter, rows, self.seq_len)
        toks = serving.pad_rows(
            jnp.asarray(np.stack([r.prompt for r in reqs]), jnp.int32), rows)
        logits = None
        if self.adapter.server_prefill is not None:
            chunk_fn = serving.make_prefill_chunk(self.adapter,
                                                  self.n_clients,
                                                  self.seq_len)
            for t0, t1, m in serving.prefill_plan(prompt_len, self.span):
                prog, dt = serving.compiled_with_timing(
                    chunk_fn, self.params, toks[:, t0:t1], caches, t0, m)
                self.compile_s += dt
                self._close_drained()
                logits, caches, stats = prog(self.params, toks[:, t0:t1],
                                             caches, t0, m)
                if stats:
                    self._count_wave_load(stats["moe_load"], w, t1 - t0)
        else:
            step = serving.make_serve_step(self.adapter, self.n_clients,
                                           self.seq_len)
            prog, dt = serving.compiled_with_timing(
                step, self.params, toks[:, :1], caches, 0)
            self.compile_s += dt
            self._close_drained()
            for t in range(prompt_len):
                logits, caches = prog(self.params, toks[:, t:t + 1],
                                      caches, t)
        if w == 1:
            self._prefill_caches = caches
        return logits, caches

    @tags.host_boundary("preemption-resume replay: feeds the victim's "
                        "already-fetched host tokens back one position at "
                        "a time — host->device uploads on a cold path, "
                        "never the steady-state decode loop")
    def _replay_generated(self, req: ServeRequest, logits, caches):
        """Re-derive a preempted request's device state: feed its
        already-generated tokens through the per-token serve step, one
        position at a time — the exact computation the solo decode loop
        runs, so the carried logits and cache rows come back bitwise and
        the resumed stream continues where the evicted one stopped."""
        step = serving.make_serve_step(self.adapter, self.n_clients,
                                       self.seq_len)
        pl = req.prompt.size
        rows = serving.MIN_ROWS        # the width-1 wave's padded buffer
        tok0 = np.full((rows, 1), req.generated[0], np.int32)
        prog, dt = serving.compiled_with_timing(
            step, self.params, tok0, caches, pl)
        self.compile_s += dt
        self._close_drained()
        for i, tok in enumerate(np.asarray(req.generated, np.int32)):
            logits, caches = prog(self.params,
                                  np.full((rows, 1), tok, np.int32),
                                  caches, pl + i)
        return logits, caches

    def _admit_wave(self, slots: List[int], reqs: List[ServeRequest]):
        """Prefill a wave of requests, allocate their pages, and install
        all their slot state with ONE compiled scatter — async dispatches
        only, no host sync. Prefill wire traffic is logged here per
        request: one embedding upload per prefilled position (prompt
        only for fresh requests; prompt + replayed tokens for a resumed
        one), no downlink."""
        w = len(reqs)
        prompt_len = reqs[0].prompt.size
        gens = [int(r.generated.size) for r in reqs]
        eff_len = prompt_len + gens[0]      # uniform: wave is width-1 when
        assert all(g == gens[0] for g in gens)  # any prefix is non-empty
        pages = [self.allocator.alloc(paging.pages_needed(
            r.prompt.size + r.gen_len, self.page_size)) for r in reqs]

        with spans.span("vfl.sched.prefill_wave", width=w,
                        prompt_len=prompt_len):
            logits, caches = self._prefill_wave(reqs)
        if gens[0]:
            logits, caches = self._replay_generated(reqs[0], logits, caches)
            if w == 1:
                self._prefill_caches = caches
        if self._logits_st is None:
            self._logits_st = jnp.zeros(
                (self.n_rows, 1) + logits.shape[1:], logits.dtype)

        with spans.span("vfl.sched.install", width=w):
            rows = jnp.asarray(np.stack([
                paging.install_rows(p, eff_len, self.page_size)
                for p in pages]))
            kd = np.stack([np.asarray(jax.random.key_data(r.key))
                           for r in reqs])
            gen_rows = np.zeros((w, self.seq_len), np.int32)
            for i, r in enumerate(reqs):
                gen_rows[i, :r.generated.size] = r.generated
            fn = make_install_prog(self.adapter, self.seq_len)
            args = (self._caches_st, self._logits_st, self._t_st,
                    self._gen_pos_st, self._rem_st, self._keydata_st,
                    self._gen_buf_st, caches, logits, rows,
                    np.asarray(slots, np.int32),
                    np.full(w, eff_len, np.int32),
                    np.asarray([r.gen_len - g
                                for r, g in zip(reqs, gens)], np.int32),
                    kd, gen_rows, np.asarray(gens, np.int32))
            prog, dt = serving.compiled_with_timing(fn, *args)
            self.compile_s += dt
            self._close_drained()
            (self._caches_st, self._logits_st, self._t_st,
             self._gen_pos_st, self._rem_st, self._keydata_st,
             self._gen_buf_st) = prog(*args)
        self.admitted += w
        self.prefill_waves += 1

        for slot, req, page_ids in zip(slots, reqs, pages):
            self._tables[slot, :] = paging.ZERO_PAGE
            self._tables[slot, :len(page_ids)] = page_ids
            self._tables_dev = None
            self._slot_pages[slot] = page_ids
            self._slot_req[slot] = req
            self._remaining[slot] = req.gen_len - req.generated.size
            self._admitted_at[slot] = self.steps
            if req.first_admitted < 0:
                req.first_admitted = self.steps
            self.transport.account_serve(
                batch=1, embed=self.embed_dim,
                n_steps=req.prompt.size + req.generated.size, n_gen=0,
                ledger=req.ledger)

    def _expire_queue(self):
        """Fail queued requests that can no longer meet their deadline
        (an admitted request always retires in exactly ``remaining``
        scheduler steps, so feasibility is checkable at admission)."""
        i = 0
        while i < len(self._queue):
            req = self._queue[i]
            needed = req.gen_len - req.generated.size
            if (req.deadline is not None
                    and self.steps + needed > req.deadline):
                self._queue.pop(i)
                self.deadline_misses += 1
                self._fail_request(req, "deadline")
            else:
                i += 1

    def _pick_victim(self) -> Optional[int]:
        """Preemption victim: the occupied slot with the FEWEST tokens
        remaining, among slots that produced at least one token since
        (re-)admission — requiring progress makes preemption ping-pong
        terminate (total remaining strictly decreases between evictions
        of the same pair)."""
        best, best_rem = None, None
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            ran = (req.gen_len - req.generated.size) - self._remaining[slot]
            if ran <= 0:
                continue
            if best_rem is None or self._remaining[slot] < best_rem:
                best, best_rem = slot, self._remaining[slot]
        return best

    def _admit_free_slots(self):
        """FIFO wave admission: take the queue's head run of equal-length
        prompts that fits the free slots AND the page pool, prefill it as
        one batch and install it with one compiled scatter. The queue is
        never reordered — if the head doesn't fit, nothing jumps it.
        With ``preempt=True`` a page-starved head may evict a victim
        (see :meth:`_pick_victim`) instead of waiting."""
        while self._queue:
            self._expire_queue()
            if not self._queue:
                return
            free = [s for s in range(self.max_batch)
                    if self._slot_req[s] is None]
            if not free:
                return
            avail = self.allocator.available
            pl = self._queue[0].prompt.size
            g0 = int(self._queue[0].generated.size)
            wave = []
            for req in self._queue:
                need = paging.pages_needed(req.prompt.size + req.gen_len,
                                           self.page_size)
                if (len(wave) == len(free) or req.prompt.size != pl
                        or need > avail
                        or int(req.generated.size) != g0
                        or (g0 and wave)):
                    break
                wave.append(req)
                avail -= need
            if not wave:
                # page-gated. Either preempt a victim to unblock the
                # head, or wait for a retirement wave to free pages.
                if self.preempt:
                    victim = self._pick_victim()
                    if victim is not None:
                        self._preempt_slot(victim)
                        continue
                return
            del self._queue[:len(wave)]
            now = time.time_ns()
            for req in wave:
                spans.record("vfl.sched.queued", req.queued_ns, now,
                             rid=req.rid)
            with spans.span("vfl.sched.admit",
                            rids=[req.rid for req in wave]):
                self._admit_wave(free[:len(wave)], wave)

    # ----------------------------------------------------- the engine ----
    def _block_len(self, budget: Optional[int] = None) -> int:
        occ = [s for s, r in enumerate(self._slot_req) if r is not None]
        m = int(min(self._remaining[s] for s in occ))
        if budget is not None:
            m = min(m, max(int(budget), 1))
        return 1 << (max(m, 1).bit_length() - 1)    # pow2 floor <= min rem

    def _device_tables(self):
        """Device mirror of the block tables, uploaded once per mutation
        (admission / retirement) instead of once per block — the first
        scheduler revision re-uploaded an identical table every block."""
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self._tables)
        return self._tables_dev

    @tags.hot_loop
    def _block_step(self, budget: Optional[int] = None):
        """Run one compiled K-step decode block over all slots — one
        dispatch, zero host syncs."""
        n_occ = self.active
        if n_occ == 0:
            return
        k = self._block_len(budget)
        with spans.span("vfl.sched.block", k=k, occupancy=n_occ):
            prog = self._block_progs.get(k)
            tables = self._device_tables()
            args = (self.params, tables, self._keydata_st, self._logits_st,
                    self._caches_st, self._t_st, self._gen_pos_st,
                    self._rem_st, self._gen_buf_st)
            if prog is None:
                block_fn = make_paged_decode_block(
                    self.adapter, self.n_clients, self.seq_len,
                    self.temperature, self.vocab_size, self.page_size,
                    self.n_rows, k)
                prog, dt = serving.compiled_with_timing(block_fn, *args)
                self.compile_s += dt
                self._block_progs[k] = prog
            self._close_drained()
            (self._logits_st, self._caches_st, self._t_st, self._gen_pos_st,
             self._rem_st, self._gen_buf_st, pairs) = prog(*args)
            if pairs:
                self._moe_acc = _add_decode_pairs(self._moe_acc,
                                                  pairs["moe_load"])
                self.expert_tokens += k * n_occ * self._moe_shape[0]
        self.steps += k
        self.generated_tokens += k * n_occ
        for slot, req in enumerate(self._slot_req):
            if req is not None:
                self._remaining[slot] -= k

    # ---------------------------------------------------- slot teardown --
    @tags.host_boundary("eviction fetch: one device->host transfer pulls "
                        "the slot's generated-so-far tokens and its "
                        "logits-health flag — preempt/cancel/poison paths "
                        "only, never the hot loop")
    def _fetch_slot(self, slot: int):
        """(tokens generated so far, logits finite?) for one slot."""
        req = self._slot_req[slot]
        total = (req.gen_len - req.generated.size) - self._remaining[slot]
        total += req.generated.size
        toks = np.asarray(self._gen_buf_st[slot])[:int(total)]
        finite = True
        if self._logits_st is not None:
            finite = bool(np.isfinite(np.asarray(
                self._logits_st[slot], np.float32)).all())
        self._drained = (time.time_ns(), "evict")
        self.host_transfers += 1
        return toks.astype(np.int32), finite

    def _scrub_pages(self, page_ids) -> None:
        """Zero a poisoned request's pages (and the trash page) in every
        pooled leaf before they can be reallocated. Ordinary stale bytes
        sit behind the causal mask and contribute exactly 0.0; NaN does
        not (0·NaN = NaN), so poison must not outlive its tenancy."""
        pages = jnp.asarray(np.concatenate(
            [np.asarray(page_ids, np.int32),
             np.asarray([paging.TRASH_PAGE], np.int32)]))
        self._caches_st = jax.tree.map(
            lambda st, plan: (st.at[:, pages].set(jnp.zeros(
                (), st.dtype)) if plan.pooled else st),
            self._caches_st, self._plans)

    def _release_slot(self, slot: int, *, scrub: bool) -> None:
        """Return a slot's pages to the pool and deactivate its device
        row (``rem=0`` — otherwise the freed slot would keep decoding
        and scribble on the ZERO page via its reset table)."""
        if scrub:
            self._scrub_pages(self._slot_pages[slot])
        self.allocator.free_(self._slot_pages[slot])
        self._slot_pages[slot] = None
        self._tables[slot, :] = paging.ZERO_PAGE
        self._tables_dev = None
        self._slot_req[slot] = None
        self._remaining[slot] = 0
        self._rem_st = self._rem_st.at[slot].set(0)

    def _fail_request(self, req: ServeRequest, status: str
                      ) -> RequestResult:
        res = RequestResult(
            rid=req.rid, tokens=np.asarray(req.generated, np.int32),
            ledger=req.ledger, prompt_len=int(req.prompt.size),
            admitted_at=int(req.first_admitted), finished_at=self.steps,
            status=status, preemptions=req.preemptions)
        self._results[req.rid] = res
        return res

    def _evict_slot(self, slot: int, status: str) -> RequestResult:
        """Terminally evict an in-flight request (cancel / poison): meter
        the generation steps that actually ran, free (and if poisoned,
        scrub) its pages, record the partial result."""
        req = self._slot_req[slot]
        toks, finite = self._fetch_slot(slot)
        ran = len(toks) - req.generated.size
        if ran > 0:
            self.transport.account_serve(batch=1, embed=self.embed_dim,
                                         n_steps=ran, n_gen=ran,
                                         ledger=req.ledger)
        if not finite:
            status = "poisoned"
            self.poisoned += 1
        self._release_slot(slot, scrub=not finite)
        req.generated = toks
        return self._fail_request(req, status)

    def _preempt_slot(self, slot: int) -> None:
        """Evict a victim to free pages for the queue's head: fetch its
        tokens so far, meter the evicted tenancy, and re-queue it (tail)
        to re-prefill + replay later. A poisoned victim fails here
        instead of being resumed (replaying NaN state is pointless)."""
        req = self._slot_req[slot]
        toks, finite = self._fetch_slot(slot)
        ran = len(toks) - req.generated.size
        if ran > 0:
            self.transport.account_serve(batch=1, embed=self.embed_dim,
                                         n_steps=ran, n_gen=ran,
                                         ledger=req.ledger)
        if not finite:
            self.poisoned += 1
            self._release_slot(slot, scrub=True)
            req.generated = toks
            self._fail_request(req, "poisoned")
            return
        self._release_slot(slot, scrub=False)
        req.generated = toks
        req.preemptions += 1
        self.preemptions += 1
        req.queued_ns = time.time_ns()
        self._queue.append(req)

    @tags.host_boundary("once-per-wave retirement fetch: one batched "
                        "device->host transfer covers every slot that "
                        "finished in the last block — O(requests) syncs, "
                        "not O(steps)")
    def _retire_wave(self):
        """Retire every slot that finished in the last block: ONE
        batched device→host fetch for all of them, generation wire
        accounted in one deferred call per request (byte-identical to
        the per-step metering it replaces — see the module docstring).
        The same fetch carries each slot's logits-health flag: a
        non-finite slot fails as ``status="poisoned"`` and its pages are
        scrubbed before reuse."""
        done = [s for s, r in enumerate(self._slot_req)
                if r is not None and self._remaining[s] <= 0]
        if not done:
            return
        with spans.span("vfl.sched.retire", n=len(done)):
            done_idx = jnp.asarray(np.array(done, np.int32))
            with spans.span("vfl.sched.retire_fetch"):
                toks_all = np.asarray(self._gen_buf_st[done_idx])
                fin_all = np.isfinite(np.asarray(
                    self._logits_st[done_idx], np.float32)).reshape(
                        len(done), -1).all(axis=1)
            self._drained = (time.time_ns(), "retire")
            self.host_transfers += 1
            for row, slot in enumerate(done):
                req = self._slot_req[slot]
                ran = req.gen_len - req.generated.size
                self.transport.account_serve(batch=1, embed=self.embed_dim,
                                             n_steps=ran, n_gen=ran,
                                             ledger=req.ledger)
                finite = bool(fin_all[row])
                if not finite:
                    self.poisoned += 1
                self._results[req.rid] = RequestResult(
                    rid=req.rid, tokens=toks_all[row, :req.gen_len],
                    ledger=req.ledger, prompt_len=req.prompt.size,
                    admitted_at=int(self._admitted_at[slot]),
                    finished_at=self.steps,
                    status="ok" if finite else "poisoned",
                    preemptions=req.preemptions)
                self._release_slot(slot, scrub=not finite)

    # --------------------------------------------------- expert layers ----
    def _count_wave_load(self, load, w: int, chunk: int) -> None:
        """Add one prefill chunk's held-expert load to the device-side
        counters (an async dispatch; nothing is read back)."""
        self._moe_shape = (load.shape[0], load.shape[2])
        if self._moe_acc is None:
            self._moe_acc = {k: jnp.zeros((), jnp.int32) for k in
                             ("pairs", "wave_load_max", "wave_load_sum")}
        real = (np.arange(load.shape[1]) < w).astype(np.int32)
        self._moe_acc = _add_wave_load(self._moe_acc, load, real)
        self.expert_tokens += w * chunk * load.shape[0]

    def moe_counters(self) -> Optional[Dict[str, Any]]:
        """Counters of the expert layers that hold a share, None where
        they hold every expert: ``tokens`` through the expert layers
        (each layer counts a token), ``pairs`` of them routed to held
        experts, and over the prefill wave steps (a chunk through one
        expert layer) the sums of the busiest held expert's pairs
        (``wave_load_max``) and of all held pairs (``wave_load_sum``);
        ``held`` experts. Only the admitted rows of a wave and the
        active slots of a decode step count. The device counters come
        back as device scalars, unread: reading them syncs."""
        if self._moe_acc is None:
            return None
        return {"tokens": self.expert_tokens, "held": self._moe_shape[1],
                **self._moe_acc}

    def _record_moe(self) -> None:
        """Keep the expert counters as a ``vfl.sched.moe`` record while a
        profiler session records (a reader takes the difference of two)."""
        c = self.moe_counters()
        if c is not None:
            now = time.time_ns()
            spans.record("vfl.sched.moe", now, now, **c)

    # ----------------------------------------------------------- drive ----
    def _close_drained(self) -> None:
        """A compiled program is about to be queued (or ``run`` is
        returning): close the drained stretch, if one is open."""
        if self._drained is not None:
            since, after = self._drained
            spans.record("vfl.sched.drained", since, time.time_ns(),
                         after=after)
            self._drained = None

    @property
    def active(self) -> int:
        return sum(r is not None for r in self._slot_req)

    @property
    def pending(self) -> int:
        return len(self._queue)

    def run(self, max_steps: Optional[int] = None) -> List[RequestResult]:
        """Drain the queue: admit into free slots (and free pages) as
        they open up mid-flight, run compiled decode blocks until every
        submitted request is done. Returns the requests that reached a
        terminal state DURING this call, in rid order (earlier drains
        stay retrievable via ``results``); wall-clock minus compile is
        exposed as ``last_run_s``.

        ``max_steps`` bounds the scheduler steps executed this call
        (blocks are shortened to land exactly on the bound) and returns
        with work still in flight — the partial-drain hook that
        :meth:`snapshot`, :meth:`cancel` and kill/resume tests interleave
        with."""
        before = set(self._results)
        tic = time.perf_counter()
        compile0 = self.compile_s
        start = self.steps
        with spans.span("vfl.sched.run"):
            self._record_moe()
            self._drained = (time.time_ns(), "entry")
            while self._queue or self.active:
                budget = (None if max_steps is None
                          else max_steps - (self.steps - start))
                if budget is not None and budget <= 0:
                    break
                self._admit_free_slots()
                self._block_step(budget)
                self._retire_wave()
            jax.block_until_ready(self._gen_buf_st)
            self._close_drained()
            self._record_moe()
        self.last_run_s = (time.perf_counter() - tic
                           - (self.compile_s - compile0))
        return [self._results[rid]
                for rid in sorted(set(self._results) - before)]

    @property
    def results(self) -> Dict[int, RequestResult]:
        """Every request this scheduler has ever drained, by rid."""
        return dict(self._results)

    # ------------------------------------------------------ durability ----
    def _req_meta(self, req: ServeRequest, *, remaining: int,
                  admitted_at: int) -> dict:
        return {
            "rid": req.rid, "prompt": np.asarray(req.prompt).tolist(),
            "gen_len": int(req.gen_len),
            "key_data": np.asarray(
                jax.random.key_data(req.key)).tolist(),
            "deadline": req.deadline,
            "generated": np.asarray(req.generated).tolist(),
            "preemptions": int(req.preemptions),
            "first_admitted": int(req.first_admitted),
            "ledger": _ledger_rows(req.ledger),
            "remaining": int(remaining),
            "admitted_at": int(admitted_at),
        }

    @staticmethod
    def _req_from_meta(d: dict) -> ServeRequest:
        kd = jnp.asarray(np.asarray(d["key_data"], np.uint32))
        return ServeRequest(
            rid=int(d["rid"]),
            prompt=np.asarray(d["prompt"], np.int32),
            gen_len=int(d["gen_len"]),
            key=jax.random.wrap_key_data(kd),
            ledger=_ledger_from_rows(d["ledger"]),
            deadline=d["deadline"],
            generated=np.asarray(d["generated"], np.int32),
            preemptions=int(d["preemptions"]),
            first_admitted=int(d["first_admitted"]))

    @tags.host_boundary("snapshot fetch: pulls the whole serve-plane "
                        "device state (page pool, slot rows, gen buffers, "
                        "key streams) to host for a durable checkpoint — "
                        "a stop-the-world operation, never the hot loop")
    def snapshot(self) -> SchedulerState:
        """Capture the complete serve plane between blocks. The snapshot
        is self-contained: restored via ``fed.serve(params, state=...)``
        the scheduler continues the drain with bitwise-identical token
        streams and byte-identical per-request ledgers."""
        jax.block_until_ready(self._gen_buf_st)
        flat: Dict[str, np.ndarray] = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self._caches_st)[0]:
            flat[_leafkey("caches", path)] = np.asarray(leaf)
        slot_arrays = {
            "t": self._t_st, "gen_pos": self._gen_pos_st,
            "rem": self._rem_st, "gen_buf": self._gen_buf_st,
            "keydata": self._keydata_st, "tables": self._tables,
        }
        if self._logits_st is not None:
            slot_arrays["logits"] = self._logits_st
        for name, arr in slot_arrays.items():
            flat[f"slot_{name}"] = np.asarray(arr)
        meta = {
            "config": {
                "max_batch": self.max_batch, "seq_len": self.seq_len,
                "n_clients": self.n_clients, "embed_dim": self.embed_dim,
                "vocab_size": self.vocab_size,
                "temperature": self.temperature,
                "page_size": self.page_size, "n_pages": self.n_pages,
                "max_queue": self.max_queue, "preempt": self.preempt,
                "has_logits": self._logits_st is not None,
            },
            "allocator": self.allocator.snapshot(),
            "slots": [None if req is None else self._req_meta(
                req, remaining=int(self._remaining[s]),
                admitted_at=int(self._admitted_at[s]))
                for s, req in enumerate(self._slot_req)],
            "slot_pages": [None if p is None else
                           np.asarray(p).tolist()
                           for p in self._slot_pages],
            "queue": [self._req_meta(r, remaining=0, admitted_at=-1)
                      for r in self._queue],
            "results": [{
                "rid": r.rid, "tokens": np.asarray(r.tokens).tolist(),
                "ledger": _ledger_rows(r.ledger),
                "prompt_len": int(r.prompt_len),
                "admitted_at": int(r.admitted_at),
                "finished_at": int(r.finished_at), "status": r.status,
                "preemptions": int(r.preemptions),
            } for r in self._results.values()],
            "counters": {
                "steps": self.steps, "next_rid": self._next_rid,
                "generated_tokens": self.generated_tokens,
                "host_transfers": self.host_transfers,
                "preemptions": self.preemptions,
                "deadline_misses": self.deadline_misses,
                "poisoned": self.poisoned,
                "admitted": self.admitted,
                "prefill_waves": self.prefill_waves,
            },
        }
        moe = self.moe_counters()
        if moe is not None:
            meta["counters"]["moe"] = {k: int(v) for k, v in moe.items()}
            meta["counters"]["moe_layers"] = self._moe_shape[0]
        return SchedulerState(flat=flat, meta=meta)

    @tags.host_boundary("checkpoint restore: rehydrates host-side queue/"
                        "slot/result metadata and uploads the pooled "
                        "caches once — runs before the first decode "
                        "block, never inside it")
    def _load_state(self, state: SchedulerState) -> None:
        cfg = state.meta["config"]
        for k in ("max_batch", "seq_len", "n_clients", "page_size",
                  "n_pages"):
            if int(cfg[k]) != int(getattr(self, k)):
                raise ValueError(
                    f"serve state was captured with {k}={cfg[k]}, this "
                    f"scheduler has {getattr(self, k)} — construct via "
                    "fed.serve(params, state=...) so the config matches")
        leaves, treedef = jax.tree_util.tree_flatten_with_path(
            self._caches_st)
        self._caches_st = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(state.flat[_leafkey("caches", p)],
                                  dtype=leaf.dtype)
                      for p, leaf in leaves])
        self._t_st = jnp.asarray(state.flat["slot_t"])
        self._gen_pos_st = jnp.asarray(state.flat["slot_gen_pos"])
        self._rem_st = jnp.asarray(state.flat["slot_rem"])
        self._gen_buf_st = jnp.asarray(state.flat["slot_gen_buf"])
        self._keydata_st = jnp.asarray(state.flat["slot_keydata"])
        # copy: the snapshot array may be a read-only npz view (or alias
        # a live scheduler's table), and _tables is mutated in place
        self._tables = np.array(state.flat["slot_tables"], np.int32)
        self._tables_dev = None
        if cfg["has_logits"]:
            self._logits_st = jnp.asarray(state.flat["slot_logits"])
        self.allocator = paging.PageAllocator.restore(
            state.meta["allocator"])
        self._slot_req = [None if d is None else self._req_from_meta(d)
                          for d in state.meta["slots"]]
        self._slot_pages = [None if p is None else
                            np.asarray(p, np.int32)
                            for p in state.meta["slot_pages"]]
        self._remaining = np.zeros(self.max_batch, np.int64)
        self._admitted_at = np.zeros(self.max_batch, np.int64)
        for s, d in enumerate(state.meta["slots"]):
            if d is not None:
                self._remaining[s] = int(d["remaining"])
                self._admitted_at[s] = int(d["admitted_at"])
        self._queue = [self._req_from_meta(d)
                       for d in state.meta["queue"]]
        self._results = {}
        for d in state.meta["results"]:
            self._results[int(d["rid"])] = RequestResult(
                rid=int(d["rid"]),
                tokens=np.asarray(d["tokens"], np.int32),
                ledger=_ledger_from_rows(d["ledger"]),
                prompt_len=int(d["prompt_len"]),
                admitted_at=int(d["admitted_at"]),
                finished_at=int(d["finished_at"]),
                status=d["status"], preemptions=int(d["preemptions"]))
        c = state.meta["counters"]
        self.steps = int(c["steps"])
        self._next_rid = int(c["next_rid"])
        self.generated_tokens = int(c["generated_tokens"])
        self.host_transfers = int(c["host_transfers"])
        self.preemptions = int(c["preemptions"])
        self.deadline_misses = int(c["deadline_misses"])
        self.poisoned = int(c["poisoned"])
        self.admitted = int(c.get("admitted", 0))
        self.prefill_waves = int(c.get("prefill_waves", 0))
        if "moe" in c:
            moe = c["moe"]
            self.expert_tokens = int(moe["tokens"])
            self._moe_shape = (int(c["moe_layers"]), int(moe["held"]))
            self._moe_acc = {k: jnp.asarray(int(moe[k]), jnp.int32) for k
                             in ("pairs", "wave_load_max", "wave_load_sum")}
