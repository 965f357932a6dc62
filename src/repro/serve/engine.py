"""Slot-based continuous-batching serve engine.

The paper treats each vector lane as an independent low-precision
element sharing one datapath; the serving analogue implemented here
treats each batch slot as an independent *sequence* sharing one compiled
program.  Concretely:

* **Per-slot decode positions.**  ``decode_step`` takes a ``(B,)``
  position vector, so every slot decodes at its own offset — positions
  are data, not shape, and one compilation serves every mix of request
  lengths.
* **Paged KV cache.**  The engine keeps KV in shared ``(num_pages,
  page_size, ...)`` pools plus a ``(batch, max_pages)`` int32 page
  table — the paper's fixed-width-reusable-unit idea applied to KV
  storage — and owns that geometry (``ServeConfig.page_size`` /
  ``num_pages``).  Page ids are data, not shape, so allocation, refill
  and recycling never recompile; a host-side free-list allocator
  (``serve.paging``) hands pages out at admission and takes them back
  at completion, and admission *defers* (backpressure) instead of
  OOMing when the pool is exhausted.  Cache HBM then scales with live
  tokens, not ``batch × max_len``.
* **Incremental page allocation.**  ``alloc_mode="reserve"`` books a
  request's worst-case page count up front; ``alloc_mode="incremental"``
  books only the prompt pages (plus the first decode page) and tops a
  slot up right before any decode chunk whose writes would cross its
  allocated page boundary (``PageTable.extend`` — still data, not
  shape).  Early-EOS requests never touch their unbooked tail, so the
  same pool sustains more concurrent requests (overcommit: ``num_pages``
  may sit below the sum of worst-case page counts).
* **Preemption.**  When an incremental top-up finds the pool dry, or a
  strictly-higher-effective-priority arrival cannot get a slot or
  pages, the weakest running slot is evicted: its pages return to the
  pool and the request re-enters the queue *carrying its generated
  tokens*.  On re-admission the prompt is re-prefilled (same compiled
  prefill) and the generated tokens are teacher-forced back through the
  decode chunk — the client-visible stream is preserved verbatim and a
  preempted greedy stream resumes **bit-identically** to an
  uninterrupted one.  Eviction uses the same aging-adjusted effective
  priority as admission, so equal-priority requests never ping-pong.
* **Prefill into a free slot.**  A new request is prefilled alone
  (batch 1), padded to the slot prompt budget (``prefill_len``), and its
  whole prompt *pages* are copied into the slot's pool pages
  (``merge_slot_paged_caches``).  Pad-token cache rows are harmless:
  decode overwrites row ``p`` before any query can attend to it.
* **Prefix caching (copy-on-write pages).**  With
  ``prefix_cache=True`` the engine keeps a host-side
  index from page-aligned prompt-chunk hashes to the pool pages holding
  their KV rows (``serve.paging.PrefixCache``).  Admission maps the
  longest cached prefix into the new slot's page-table row *read-only*
  (the allocator refcounts holders) and prefills **only the uncached
  suffix** through the same compiled prefill program — the suffix sits
  in the padded prompt buffer, a traced ``start`` carries its global
  position, and every attention layer splices the gathered cached rows
  below the fresh ones at the fixed buffer length, so a cache miss is
  bit-identical to a no-cache engine and a hit reuses the paper's
  logic-reuse idea one level up (compute the shared operand once,
  reuse it across consumers).  When a prompt is *fully* covered by
  cached pages, the tail page is **copy-on-written** inside the same
  program (duplicated into a private page before the last token's KV
  write could land on shared storage).  Completion and eviction
  *decrement* refcounts instead of freeing outright, so a victim's
  shared pages survive for their other holders, and cold index entries
  are reclaimed LRU-leaf-first under pool pressure.
* **Priority scheduling.**  The request queue is a priority heap
  (``Request.priority``, higher first; arrival time then submission
  order break ties) with simple aging — every ``priority_aging_s``
  seconds of waiting adds one effective priority level, so long prompts
  can no longer head-of-line-block short high-priority ones and starved
  low-priority requests eventually win.
* **Per-slot completion.**  Each slot tracks its own remaining-token
  budget and optional ``eos_id``; finished slots are refilled from the
  request queue between decode chunks without recompiling anything
  (``Engine.compile_counts`` stays at one entry per function — counted
  by an engine-owned signature tracker, not a jax-private probe).
* **Jitted multi-token decode.**  The inner loop is a ``lax.scan`` over
  ``decode_chunk`` tokens inside a single ``jax.jit`` — one dispatch
  per chunk, not per token.
* **Sampling.**  Every generated token, including the first one after
  prefill, goes through the same temperature/greedy path.  The rng for
  stream index ``i`` of request ``r`` is *index-derived* —
  ``fold_in(fold_in(base_key, r.id), i)`` — never a split chain
  threaded through the decode loop, so a draw depends only on (request,
  position), not on batch composition, admission order, or how many
  chunks ran before it.  Sampled streams are therefore bit-stable under
  preemption and resume, exactly like greedy ones.
* **Self-speculative decoding.**  With ``spec_decode=True`` the
  *quantized* execution mode of the same weights drafts ``spec_k``
  tokens per slot (a ``lax.scan`` under the draft config), and ONE
  dense multi-token ``decode_step`` forward verifies all draft
  positions at once (the multi-position machinery above, at
  ``S = spec_k + 1``) — the paper's logic-reuse pairing: the low-power
  nibble datapath proposes, the full-precision datapath it was carved
  from disposes.  Greedy acceptance is exact-match, so a spec stream is
  bit-identical to the non-spec dense stream; at temperature > 0
  rejection sampling preserves the dense distribution.  Rejected draft
  tails roll back as a **page-table operation** — ``PageTable.truncate``
  re-points the dead tail at the trash page and the allocator takes the
  pages back; no cache rows are copied (the dense verify already
  overwrote the draft's rows, and junk rows past the accepted prefix
  are never attended before their owner rewrites them).  The engine
  compiles exactly one draft and one verify program (``compile_counts``
  keeps ``{"prefill": 1, "draft": 1, "verify": 1}``; the plain decode
  chunk is never built in spec mode).

**Tail latency** (``prefill_chunk`` / ``admit_group`` / ``swap_mode``).
Three mechanisms bound the scheduler-level stalls heavy traffic hits:

* *Chunked prefill*: with ``prefill_chunk > 0`` (or ``admit_group >
  1``) admission only books pages and parks the slot in a *prefilling*
  state; each scheduler step then advances up to ``admit_group`` such
  slots by one ``prefill_chunk``-token chunk through ONE compiled wave
  program — ``decode_step`` with an (G, C) token block at per-lane
  global positions, the same multi-position paged scatter/gather the
  spec verify forward uses — *before* the running slots' decode chunk.
  A giant admitted prompt therefore costs running slots one chunk of
  latency per step instead of one monolithic prefill, and the
  monolithic prefill program is never built (``compile_counts`` pins
  ``{"prefill": 0, "prefill_chunk": 1}``).
* *Grouped admission*: simultaneous arrivals admitted in one window
  become multiple prefilling slots, and every wave batches up to
  ``admit_group`` of them into one padded (G, C) dispatch — burst
  admission costs one program launch, not G serialized batch-1
  prefills.  Greedy wave streams bit-match monolithic serialized
  admission (the dense chunk computation is bit-exact; quantized modes
  are argmax-stable, as everywhere per-tensor activation scales make
  streams batch-composition-dependent).
* *Host-tier page swap*: with ``swap_mode="host"`` eviction copies the
  victim's live KV pages into a ``HostPagePool`` (host RAM, same
  refcount discipline as the device allocator) and resume copies them
  back into fresh pages and re-points the table — O(pages) copies
  replace the O(generated_len) replay decode steps, and the restore is
  a bit-copy, so even temperature/spec streams resume bit-stable.  The
  same pool backs the prefix cache's *cold tier*: reclaimed index
  entries demote to host pages instead of vanishing and promote back
  on a later hit, giving the index a capacity tier bigger than HBM.
  A full host pool degrades gracefully to replay-resume / plain
  reclaim.

Limits (tracked in ROADMAP "Open items"): models with mamba mixers
prefill at exact prompt length (end-padding would pollute the SSM
state), which recompiles per distinct prompt length, and cannot draft
multi-token speculative rounds (conv/SSM state rollback is not a
page-table operation), so ``spec_decode`` rejects them — and their
recurrent state is per-slot rather than paged, so chunked/grouped
prefill and ``swap_mode="host"`` reject them too; resume-after-
preemption with ``swap_mode="off"`` (the default) still replays the
generated tokens through the decode chunk, and spec streams at
temperature > 0 are then distribution-preserving but not bit-stable
across preemption (the draft model's cache after resume differs from
the uninterrupted run's, which can shift acceptance boundaries —
greedy spec streams stay bit-identical; ``swap_mode="host"`` removes
the replay, and with it this caveat, whenever the host tier has room).
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, spec_split
from repro.models import (
    copy_paged_cache_page,
    decode_step,
    extract_cache_pages,
    init_paged_caches,
    insert_cache_pages,
    merge_slot_paged_caches,
    prefill,
    scatter_prefill_paged_caches,
)
from repro.models.transformer import _SEQ_CACHE_KEYS
from repro.serve.paging import (
    HostPagePool,
    PageAllocator,
    PageTable,
    PrefixCache,
    pages_needed,
)
from repro.serve.spans import (
    ADMIT,
    DECODE,
    EMIT,
    EVICT,
    IDLE,
    PREFILL,
    READBACK,
    RESUME,
    STEP,
    UPLOAD,
    WAIT,
    span,
    step_span,
)

__all__ = ["ServeConfig", "Request", "Engine"]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch: int                        # concurrent decode slots
    max_len: int                      # per-slot cache budget (tokens)
    temperature: float = 0.0          # 0 = greedy
    eos_id: int = -1                  # -1 = no EOS (length-only stopping)
    prefill_len: int = 0              # slot prompt budget: prompts are
    #   padded to this length so one prefill compilation serves every
    #   request.  0 = prefill at exact prompt length (recompiles per
    #   distinct length; always used for mamba-mixer models, where
    #   end-padding would corrupt the recurrent state).
    decode_chunk: int = 8             # tokens per jitted scan dispatch
    priority_aging_s: float = 0.0     # seconds since arrival per +1
    #   effective priority level (0 = aging off, strict priorities).
    #   Applied to queued AND running requests alike: the same measure
    #   gates preemption, so a long-waiting request climbs toward
    #   admission and, once admitted, becomes correspondingly harder to
    #   evict — equal-priority requests can never evict each other.
    alloc_mode: str = "reserve"       # page accounting:
    #   "reserve" books every request's worst-case page count at
    #   admission; "incremental" books only the prompt pages (plus the
    #   first decode page) and tops slots up per decode chunk,
    #   preempting the weakest runner when the pool runs dry — the same
    #   pool then sustains more concurrent requests (overcommit).
    page_size: int = 16               # tokens per KV pool page
    num_pages: int = 0                # pool size in pages, the trash page
    #   included; 0 = capacity parity: batch × max_len / page_size + 1,
    #   every slot's worst case at once (shrink it to bank the HBM win)
    prefix_cache: bool = False        # share read-only
    #   prompt-prefix pages across requests (hash-indexed page-aligned
    #   chunks, refcounted pages, copy-on-write on a fully covered
    #   prompt's tail page).  Admission prefills only the uncached
    #   suffix through the same compiled prefill; greedy streams stay
    #   bit-identical to an uncached engine's.  Incompatible with
    #   mamba-mixer models (recurrent state cannot compose with a
    #   cached prefix) and the int8 KV cache (cached rows would be
    #   dequantized where a solo prefill attends full precision).
    # Serving-time overrides: deploy any checkpoint under a different
    # execution mode/backend than it was configured with (the params
    # stay bf16; integer modes quantize on the fly).  ``None`` keeps the
    # model config's setting.  ``quant_backend="pallas"`` routes every
    # projection through ``ops.quant_matmul`` — the single-pass
    # plane-fused kernel with the in-kernel dequant epilogue.
    quant_mode: str | None = None
    quant_backend: str | None = None
    spec_decode: bool = False         # self-speculative decoding: the
    #   quantized (nibble) program drafts ``spec_k`` tokens per slot,
    #   then ONE multi-token dense forward verifies all draft positions
    #   at once.  Greedy acceptance keeps the emitted stream bit-equal
    #   to the non-spec dense engine's; temperature > 0 switches to
    #   rejection sampling (distribution-preserving, not bit-matching).
    #   Rejected drafts roll back as a page-table truncation — never a
    #   cache copy.  Incompatible with mamba-mixer models (the verify
    #   forward needs position-indexed caches, not recurrent state).
    spec_k: int = 4                   # draft tokens per speculation round
    spec_quant_mode: str | None = None  # draft-side QuantLinear mode;
    #   None = the engine's effective quant_mode (the deployment drafts
    #   for itself).  The verifier always runs dense — in spec mode the
    #   engine pins its prefill/verify config to quant_mode="dense" and
    #   the quant knobs configure the *draft* program only.
    prefill_chunk: int = 0            # chunked prefill: > 0 splits every
    #   admitted prompt into chunks of this many tokens, one chunk per
    #   scheduler step through a single compiled wave program
    #   (interleaved with running slots' decode chunks, so a long
    #   prompt bounds other slots' ITL impact to one chunk's latency).
    #   0 keeps the classic monolithic one-dispatch prefill — unless
    #   ``admit_group > 1``, which also enables the wave program with
    #   chunk width ``prefill_len``.  Incompatible with mamba mixers
    #   (chunk boundaries are cache positions, not recurrent state) and
    #   the int8 KV cache.
    admit_group: int = 1              # grouped admission: up to this
    #   many prefilling slots advance per wave as one padded (G, chunk)
    #   batch — a simultaneous burst costs one program launch instead of
    #   G serialized batch-1 prefills.  The group budget is fixed, so
    #   the wave program compiles exactly once.  > 1 requires (when
    #   ``prefill_chunk`` is 0) a ``prefill_len`` budget to serve as
    #   the wave width.
    swap_mode: str = "off"            # "host": on eviction copy the
    #   victim's live KV pages to a host-memory cold pool
    #   (``HostPagePool``) and restore them on resume — preemption
    #   resume becomes an O(pages) copy instead of an
    #   O(generated_len) replay, and the restore is a bit-copy, so
    #   sampled/spec streams also resume bit-stable.  The same pool
    #   gives the prefix cache a cold tier: reclaimed entries demote to
    #   host pages and promote back on a later hit.  A full host pool
    #   falls back to replay-resume.  "off" keeps replay-only resume.
    #   Incompatible with mamba mixers (recurrent state is per-slot,
    #   not paged — a restore cannot rebuild it).
    host_pages: int = 0               # host cold-pool capacity in pages
    #   for ``swap_mode="host"``; 0 = twice the device pool's
    #   allocatable capacity (host RAM is the bigger tier by design).
    prefix_cache_pages: int = 0       # capacity cap on pages the prefix
    #   index may pin: after every insert the index reclaims (LRU
    #   leaf-first, demoting to the cold tier when one is attached)
    #   down to this budget instead of only under pool pressure.
    #   Best-effort: entries whose page a live slot still maps are not
    #   reclaimable and may hold the index above the cap until that
    #   slot finishes.  0 = uncapped (pressure-driven reclaim only).
    tp: int = 1                       # tensor-parallel width: shard the
    #   weights (param_specs rules) and the paged KV/scale pools'
    #   KV-head dimension (cache_specs pool rules; in-page sequence
    #   fallback when heads don't divide) over the mesh's "model" axis,
    #   and build every compiled program with explicit in/out shardings
    #   under a (1, tp) local mesh.  The page table stays host-side and
    #   replicated.  1 = no mesh — the single-device engine, unchanged.
    #   Greedy streams under tp > 1 bit-match the single-device engine
    #   token-for-token (argmax is stable under the reduction-order
    #   shifts TP's partial-sum collectives introduce).
    mesh_shape: tuple | None = None   # explicit (data, model) in-engine
    #   mesh shape; overrides ``tp`` (the two must agree when both are
    #   given).  None = derived from ``tp``.  Data-parallelism across
    #   *requests* belongs one level up — ``serve.router.Router`` runs
    #   N single- or TP-meshed engine replicas behind one queue.


@dataclasses.dataclass
class Request:
    """One generation request (host-side bookkeeping)."""
    id: int
    prompt: np.ndarray                # (S,) int32
    max_new_tokens: int
    arrival: float = 0.0              # seconds after Engine.run() starts
    priority: int = 0                 # higher = served first (with aging)
    tokens: list = dataclasses.field(default_factory=list)  # generated
    t_first: float = -1.0             # time to first token (from run t0)
    t_done: float = -1.0
    t_tokens: list = dataclasses.field(default_factory=list)  # per-token
    #   emission times (from run t0; replayed tokens keep their original
    #   stamps) — consecutive diffs are the inter-token latencies the
    #   workload driver aggregates into ITL percentiles
    cache_rows: int = 0               # peak cache rows reserved for this
    #   request: pages × page_size (the per-request HBM footprint the
    #   benchmark reports)
    truncated: bool = False           # max_new_tokens was cut to fit the
    #   max_len budget at submit (explicit, so short output is never
    #   misread as an early EOS)
    preemptions: int = 0              # times this request was evicted
    #   mid-stream and later resumed
    chunk_keys: list | None = None    # memoized prefix-index hash chain
    #   of the prompt's page-aligned chunks (computed on first admission
    #   probe; the prompt is immutable, and admission re-plans several
    #   times per placement)
    swap_pages: list | None = None    # host page ids holding this
    #   request's swapped-out KV rows while it waits re-admission
    #   (``swap_mode="host"``); None = resume replays instead
    swap_rows: int = 0                # live cache rows captured at
    #   swap-out (= the slot's decode position then); tokens beyond
    #   ``swap_rows - len(prompt) + 1`` were not yet written back and
    #   re-enter the teacher-forcing lane on resume

    @property
    def text_len(self) -> int:
        return len(self.prompt) + len(self.tokens)


class _PriorityQueue:
    """Arrival-gated max-priority queue with lazy aging.

    Backed by a heap keyed ``(-priority, arrival, seq)``; ``pop`` takes
    the current time so not-yet-arrived requests are invisible and
    waiting requests age: every ``aging_s`` seconds in the queue adds
    one effective priority level (aging off when 0).  The common case —
    every queued request arrived, aging off — pops straight off the
    heap; otherwise the effective keys are recomputed over the (small)
    queue."""

    def __init__(self, aging_s: float = 0.0):
        self.aging_s = aging_s
        self._heap: list[tuple] = []
        self._seq = 0

    def __len__(self) -> int:
        return len(self._heap)

    def push(self, req: Request) -> None:
        heapq.heappush(self._heap, (-req.priority, req.arrival, self._seq,
                                    req))
        self._seq += 1

    def effective(self, req: Request, now: float) -> int:
        """Aging-adjusted priority.  The engine applies the same measure
        to *running* requests when picking preemption victims, so two
        equal-priority requests can never evict each other back and
        forth (both age at the same rate; strict inequality gates every
        eviction)."""
        if self.aging_s <= 0:
            return req.priority
        return req.priority + int(max(0.0, now - req.arrival)
                                  / self.aging_s)

    def next_arrival(self) -> float | None:
        return min((e[1] for e in self._heap), default=None)

    def _best_index(self, now: float) -> int | None:
        if not self._heap:
            return None
        if self.aging_s <= 0 and self._heap[0][1] <= now:
            return 0                  # heap order is the effective order
        best_i, best_key = None, None
        for i, (_, arr, seq, req) in enumerate(self._heap):
            if arr > now:
                continue
            key = (-self.effective(req, now), arr, seq)
            if best_key is None or key < best_key:
                best_i, best_key = i, key
        return best_i

    def peek(self, now: float) -> Request | None:
        """Best arrived request without removing it (the engine checks
        whether it is worth preempting a running slot for)."""
        i = self._best_index(now)
        return None if i is None else self._heap[i][3]

    def pop(self, now: float, admit: Callable[[Request], bool] = None):
        """Remove and return the best arrived request, or ``None``.
        ``admit`` vetoes the winner without removing it (admission
        backpressure defers strictly in priority order)."""
        best_i = self._best_index(now)
        if best_i is None:
            return None
        req = self._heap[best_i][3]
        if admit is not None and not admit(req):
            return None
        self._heap[best_i] = self._heap[-1]
        self._heap.pop()
        heapq.heapify(self._heap)
        return req


# stage -> the name of its jitted function.  JAX compiles a function
# ``f`` as the program ``jit_f``, and the benchmark's readers match those
# program names (``jit_chunk``, ``jit_prefill_into_slot``), so the names
# come from this one table and not from the name of the closure a
# ``_build_*`` method returns.
STAGE_FUNCTIONS = {"prefill": "prefill_into_slot", "prefill_chunk": "wave",
                   "decode_chunk": "chunk", "draft": "draft",
                   "verify": "verify"}


def _stage_body(fn, stage: str):
    """``fn`` under its stage's function name, its body inside
    ``jax.named_scope(stage)`` so every operation's metadata names the
    stage it ran in."""
    @functools.wraps(fn)
    def body(*args):
        with jax.named_scope(stage):
            return fn(*args)

    body.__name__ = body.__qualname__ = STAGE_FUNCTIONS[stage]
    return body


class _CountingJit:
    """Engine-owned compile counter around ``jax.jit``.

    ``jax.jit`` compiles once per abstract call signature — the pytree
    structure plus every leaf's shape/dtype/weak-type.  The wrapper
    derives that key per call and counts distinct keys, which makes the
    refill-without-recompile invariant checkable without the jax-private
    ``_cache_size`` probe (whose absence used to crash the serving
    benchmark on any jax upgrade that moved it).

    The recorded signatures are themselves the static-analysis surface:
    each one reconstructs (via ``abstract_args``) into a tree of
    ``ShapeDtypeStruct`` leaves that can be fed to ``jit_fn.lower`` /
    ``jit_fn.trace`` long after the run, so ``repro.staticcheck`` can
    re-lower every stage program a live engine actually compiled and
    inspect the jaxpr/HLO without re-running the workload.

    ``stage`` names an engine stage (a key of ``STAGE_FUNCTIONS``): the
    program is then compiled under the table's name and scoped by the
    stage."""

    def __init__(self, fn, stage: str | None = None, **jit_kwargs):
        if stage is not None:
            fn = _stage_body(fn, stage)
        self._fn = jax.jit(fn, **jit_kwargs)
        self._keys: set = set()

    @staticmethod
    def _leaf_sig(leaf):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return (tuple(leaf.shape), str(leaf.dtype),
                    bool(getattr(leaf, "weak_type", False)))
        return (type(leaf).__name__,)

    def __call__(self, *args):
        leaves, treedef = jax.tree_util.tree_flatten(args)
        self._keys.add((treedef, tuple(map(self._leaf_sig, leaves))))
        return self._fn(*args)

    @property
    def compile_count(self) -> int:
        return len(self._keys)

    @property
    def jit_fn(self):
        """The underlying ``jax.jit``-wrapped callable (for ``.lower`` /
        ``.trace`` against signatures returned by ``abstract_args``)."""
        return self._fn

    @property
    def signatures(self) -> tuple:
        """The distinct abstract call signatures recorded so far, in a
        deterministic order.  Each is ``(treedef, leaf_sigs)`` where
        array leaves carry ``(shape, dtype, weak_type)`` and non-array
        leaves carry ``(type_name,)``."""
        return tuple(sorted(self._keys, key=repr))

    # non-array leaves lose their value in the signature; any concrete
    # stand-in lowers to the same program because stage bodies consume
    # scalars as traced data, never as shapes
    _SCALAR_STANDIN = {"int": 0, "float": 0.0, "bool": False,
                       "NoneType": None}

    @classmethod
    def abstract_args(cls, signature) -> tuple:
        """Rebuild a recorded signature into the positional-args tuple
        of ``ShapeDtypeStruct`` leaves that ``jax.jit`` saw."""
        treedef, leaf_sigs = signature
        leaves = []
        for sig in leaf_sigs:
            if len(sig) == 3:
                shape, dtype, _weak = sig
                leaves.append(jax.ShapeDtypeStruct(shape, dtype))
            else:
                leaves.append(cls._SCALAR_STANDIN[sig[0]])
        return jax.tree_util.tree_unflatten(treedef, leaves)


def _apply_overrides(cfg: ModelConfig, scfg: ServeConfig) -> ModelConfig:
    updates = {}
    for field in ("quant_mode", "quant_backend"):
        val = getattr(scfg, field)
        if val is not None:
            updates[field] = val
    return cfg.replace(**updates) if updates else cfg


def _sampler(scfg: ServeConfig) -> Callable:
    """(B, V) logits → (B,) int32 token, greedy or temperature."""
    def sample(logits, rng):
        logits = logits.astype(jnp.float32)
        if scfg.temperature > 0.0:
            nxt = jax.random.categorical(rng, logits / scfg.temperature)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return nxt.astype(jnp.int32)

    return sample


def _slot_sampler(scfg: ServeConfig) -> Callable:
    """(B, V) logits + (B, 2) per-slot uint32 keys → (B,) int32 token.

    The per-slot keys are the index-derived stream keys (see
    ``Engine._slot_keys``): slot ``b``'s draw for stream index ``i``
    uses ``fold_in(request_key, i)``, so the draw depends only on the
    request identity and the token's position in its stream — never on
    admission order, batch composition or preemption history.  That is
    what makes *sampled* streams bit-stable under evict-and-resume."""
    def sample(logits, keys):
        logits = logits.astype(jnp.float32)
        if scfg.temperature > 0.0:
            nxt = jax.vmap(jax.random.categorical)(
                keys, logits / scfg.temperature)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return nxt.astype(jnp.int32)

    return sample


def _fold_counts(keys, counts):
    """Per-slot stream-index keys: ``fold_in(keys[b], counts[b])``."""
    return jax.vmap(jax.random.fold_in)(keys, counts)


# Sub-draw tags folded *below* the stream-index key when one token index
# needs several independent draws (speculative decoding): the chunk
# sampler's draw for index i is fold_in(req_key, i); the spec path's
# draft proposal, acceptance uniform and rejection resample for the same
# index fold one more tag in, so no draw ever aliases another.
_TAG_ACCEPT = 1
_TAG_RESAMPLE = 2
_TAG_DRAFT = 3


class Engine:
    """Continuous-batching engine: priority request queue + slot refill +
    chunked jitted decode over a paged KV cache, with incremental page
    allocation, evict-and-resume preemption and refcounted prefix
    caching (copy-on-write pages).  See
    the module docstring for the execution model and ``docs/serving.md``
    for the operator-facing reference."""

    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig,
                 devices=None):
        if scfg.prefill_len > scfg.max_len:
            raise ValueError(f"prefill_len {scfg.prefill_len} exceeds "
                             f"max_len {scfg.max_len}")
        if scfg.decode_chunk < 1:
            raise ValueError(f"decode_chunk must be >= 1, got "
                             f"{scfg.decode_chunk}")
        if scfg.alloc_mode not in ("reserve", "incremental"):
            raise ValueError(f"alloc_mode must be 'reserve' or "
                             f"'incremental', got {scfg.alloc_mode!r}")
        self.cfg = _apply_overrides(cfg, scfg)
        self.params = params
        self.scfg = scfg
        specs = (*cfg.prefix_pattern, *cfg.block_pattern,
                 *cfg.suffix_pattern)
        self._has_mamba = any(s.mixer == "mamba" for s in specs)
        self._incremental = scfg.alloc_mode == "incremental"
        ps = scfg.page_size
        if ps < 1:
            raise ValueError(f"page_size must be >= 1, got {ps}")
        if scfg.max_len % ps:
            raise ValueError(f"max_len {scfg.max_len} must be a "
                             f"multiple of page_size {ps}")
        self._page_size = ps
        self._max_pages = scfg.max_len // ps
        # page 0 is the trash page: idle slots' table rows point at it so
        # their frozen idempotent cache writes never corrupt a recycled
        # page
        self._num_pages = scfg.num_pages or scfg.batch * self._max_pages + 1
        if scfg.prefix_cache:
            if self._has_mamba:
                raise ValueError("prefix_cache=True is incompatible with "
                                 "mamba-mixer models: SSM state is "
                                 "sequential and cannot be composed from "
                                 "a cached prefix")
            if self.cfg.kv_cache_dtype == "int8":
                raise ValueError("prefix_cache=True is incompatible with "
                                 "kv_cache_dtype='int8': cached rows are "
                                 "attended dequantized while a solo "
                                 "prefill attends full precision, "
                                 "breaking the bit-match contract")
        self._spec = scfg.spec_decode
        self._draft_cfg = None
        if self._spec:
            if scfg.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {scfg.spec_k}")
            if self._has_mamba:
                raise ValueError(
                    "spec_decode=True is incompatible with mamba-mixer "
                    "models: the multi-token verify forward needs "
                    "position-indexed caches, and the recurrent state "
                    "cannot roll back a rejected draft")
            # the quantized deployment drafts for its own dense
            # verifier: the engine's effective quant knobs configure the
            # DRAFT program, while prefill + verify run pinned dense
            # (acceptance is defined against the dense model's output)
            self._draft_cfg, self.cfg = spec_split(self.cfg,
                                                   scfg.spec_quant_mode)
        if scfg.prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, got "
                             f"{scfg.prefill_chunk}")
        if scfg.admit_group < 1:
            raise ValueError(f"admit_group must be >= 1, got "
                             f"{scfg.admit_group}")
        if scfg.swap_mode not in ("off", "host"):
            raise ValueError(f"swap_mode must be 'off' or 'host', got "
                             f"{scfg.swap_mode!r}")
        if scfg.host_pages < 0:
            raise ValueError(f"host_pages must be >= 0, got "
                             f"{scfg.host_pages}")
        if scfg.prefix_cache_pages < 0:
            raise ValueError(f"prefix_cache_pages must be >= 0, got "
                             f"{scfg.prefix_cache_pages}")
        # wave mode: chunked and/or grouped prefill through one shared
        # (G, C) decode-step program at explicit global positions; the
        # monolithic prefill stage is then never built, so its pinned
        # compile count is 0 (see ``compile_counts``)
        self._wave = scfg.prefill_chunk > 0 or scfg.admit_group > 1
        if self._wave:
            if self._has_mamba:
                raise ValueError("prefill_chunk/admit_group are "
                                 "incompatible with mamba-mixer models: "
                                 "chunk boundaries are cache positions, "
                                 "and recurrent state has none")
            if self.cfg.kv_cache_dtype == "int8":
                raise ValueError("prefill_chunk/admit_group are "
                                 "incompatible with kv_cache_dtype="
                                 "'int8': earlier chunks are attended "
                                 "dequantized while a monolithic "
                                 "prefill attends full precision, "
                                 "breaking the bit-match contract")
            self._wave_chunk = scfg.prefill_chunk or scfg.prefill_len
            if self._wave_chunk < 1:
                raise ValueError("admit_group > 1 with prefill_chunk=0 "
                                 "needs prefill_len > 0 to serve as the "
                                 "wave width")
            self._wave_group = scfg.admit_group
        self._swap = scfg.swap_mode == "host"
        if self._swap and self._has_mamba:
            raise ValueError("swap_mode='host' is incompatible with "
                             "mamba-mixer models: recurrent state is "
                             "per-slot, not paged, so a page restore "
                             "cannot rebuild it")
        # TP mesh: built before the compiled stages so their explicit
        # in/out shardings can reference the sharded param/cache trees
        # (None = no mesh, the single-device engine — every jit is then
        # built without sharding kwargs, so its signatures, and with
        # them the compile_counts pins, are untouched)
        self._mesh = self._build_mesh(devices)
        # an unmeshed engine handed a device (the router's round-robin
        # placement) builds its pool there: a pool built on the default
        # device and then moved holds that device's memory until the copy
        # lands, for every replica placed elsewhere
        device = (list(devices)[0] if self._mesh is None
                  and devices is not None else None)
        with jax.default_device(device):
            self._caches = init_paged_caches(self.cfg, scfg.batch,
                                             self._num_pages, ps)
        # where params and caches are committed: the mesh, that one
        # device, or None = JAX's default device
        self._cache_sh = None
        if self._mesh is not None:
            self._shard_state()
        elif device is not None:
            from jax.sharding import SingleDeviceSharding
            self._cache_sh = SingleDeviceSharding(device)
            self.params = jax.device_put(self.params, self._cache_sh)
            self._caches = jax.device_put(self._caches, self._cache_sh)
        # the cache pool is donated: every stage rebinds it from the
        # return value, so the update happens in place instead of
        # copying every unmodified row (the out_shardings under a mesh
        # match the donated input's, so donation still applies)
        if self._wave:
            self._prefill_fn = None
            self._wave_fn = _CountingJit(self._build_wave_prefill(),
                                         "prefill_chunk", donate_argnums=1,
                                         **self._stage_shardings(9, 2))
        else:
            self._wave_fn = None
            n_pre = 10 if scfg.prefix_cache else 7
            self._prefill_fn = _CountingJit(
                self._build_prefill(), "prefill", donate_argnums=1,
                **self._stage_shardings(n_pre, 2))
        if self._spec:
            # exactly two decode-side programs — one quantized draft,
            # one dense verify; _chunk_fn is never built or called, so
            # its pinned compile count is 0 (see ``compile_counts``)
            self._chunk_fn = None
            self._draft_fn = _CountingJit(self._build_draft(), "draft",
                                          donate_argnums=1,
                                          **self._stage_shardings(10, 3))
            self._verify_fn = _CountingJit(self._build_verify(), "verify",
                                           donate_argnums=1,
                                           **self._stage_shardings(10, 3))
        else:
            self._chunk_fn = _CountingJit(self._build_decode_chunk(),
                                          "decode_chunk", donate_argnums=1,
                                          **self._stage_shardings(11, 7))
        self._next_id = 0
        self.reset()

    # ------------------------------------------------------------------
    # mesh / sharding plumbing
    # ------------------------------------------------------------------

    def _build_mesh(self, devices):
        scfg = self.scfg
        if scfg.tp < 1:
            raise ValueError(f"tp must be >= 1, got {scfg.tp}")
        shape = scfg.mesh_shape
        if shape is not None:
            shape = tuple(int(x) for x in shape)
            if len(shape) != 2:
                raise ValueError(f"mesh_shape must be (data, model), got "
                                 f"{scfg.mesh_shape!r}")
            if scfg.tp != 1 and shape[1] != scfg.tp:
                raise ValueError(f"mesh_shape {shape} disagrees with "
                                 f"tp={scfg.tp} on the model axis")
        elif scfg.tp > 1:
            shape = (1, scfg.tp)
        if shape is None or shape == (1, 1):
            return None
        from repro.launch.mesh import make_local_mesh
        return make_local_mesh(dp=shape[0], tp=shape[1], devices=devices)

    def _shard_state(self):
        """Commit the params and the cache pools to the mesh with the
        repo's partition rules: weights via ``param_specs`` (megatron
        col/row TP pairs), caches via ``cache_specs`` (pool rules: KV
        heads on "model" when divisible, in-page sequence axis
        otherwise; the page table ships replicated with every dispatch
        — see ``distributed.sharding.page_table_spec``)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import cache_specs, param_specs

        def to_shardings(specs):
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(self._mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P))

        self._param_sh = to_shardings(param_specs(self.params, self._mesh))
        self._cache_sh = to_shardings(
            cache_specs(self.cfg, self._caches, self._mesh,
                        batch=self.scfg.batch, paged=True))
        self._repl = NamedSharding(self._mesh, P())
        self.params = jax.device_put(self.params, self._param_sh)
        self._caches = jax.device_put(self._caches, self._cache_sh)

    def _stage_shardings(self, n_args: int, n_outs: int) -> dict:
        """jit kwargs for one compiled stage: params and caches keep
        their committed shardings, every other argument and output
        (tokens, positions, page-table rows, rng keys — all host-
        authored) is replicated.  Empty without a mesh, so the
        single-device jit signature is byte-identical to before."""
        if self._mesh is None:
            return {}
        r = self._repl
        return {"in_shardings": (self._param_sh, self._cache_sh)
                + (r,) * (n_args - 2),
                "out_shardings": (self._cache_sh,) + (r,) * (n_outs - 1)}

    @property
    def mesh_shape(self) -> tuple:
        """(data, model) shape of the in-engine mesh; (1, 1) unmeshed."""
        if self._mesh is None:
            return (1, 1)
        return (int(self._mesh.shape["data"]),
                int(self._mesh.shape["model"]))

    @property
    def device_count(self) -> int:
        """Devices this engine's programs span (1 without a mesh)."""
        return len(self.devices)

    @property
    def devices(self) -> tuple:
        """The devices holding this engine's params and caches."""
        if self._mesh is not None:
            return tuple(self._mesh.devices.flat)
        if self._cache_sh is not None:
            return tuple(self._cache_sh.device_set)
        return (jax.devices()[0],)

    # ------------------------------------------------------------------
    # compiled stages
    # ------------------------------------------------------------------

    def _prefill_pad_len(self, pad_len: int) -> int:
        """Cache length the prefill stage grows to: the prompt budget,
        rounded up to whole pages (the page merge copies whole pages;
        rows past the real prompt are pad garbage that decode overwrites
        or the causal mask hides)."""
        ps = self._page_size
        return -(-pad_len // ps) * ps

    def _build_prefill(self):
        cfg, scfg = self.cfg, self.scfg
        sample = _sampler(scfg)
        if scfg.prefix_cache:
            return self._build_prefix_prefill()

        def prefill_into_slot(params, caches, prompt, prompt_len, slot,
                              pages, rng):
            """prompt: (1, P) — padded; prompt_len/slot: traced scalars;
            pages: (max_pages,) traced page-id row (trash-filled past the
            request's live pages)."""
            grow_to = self._prefill_pad_len(prompt.shape[1])
            logits, one, _ = prefill(params, cfg, prompt,
                                     max_len=grow_to,
                                     logits_index=prompt_len - 1)
            caches = merge_slot_paged_caches(caches, one, slot, pages)
            first = sample(logits[:, -1], rng)[0]
            return caches, first

        return prefill_into_slot

    def _build_prefix_prefill(self):
        """Prefix-cache variant of the prefill stage: one compiled
        program serves cache miss, partial hit and fully-covered (COW)
        admissions alike — the suffix start, the page-table row and the
        COW page pair are all data, not shape."""
        cfg, scfg = self.cfg, self.scfg
        sample = _sampler(scfg)

        def prefill_into_slot(params, caches, suffix, suffix_len, slot,
                              row, start, cow_src, cow_dst, rng):
            """suffix: (1, P) padded uncached prompt tail whose first
            token sits at global position ``start`` (= rows already
            mapped read-only through ``row``); ``cow_src``/``cow_dst``
            duplicate a shared tail page into a private one *before*
            any write (the no-COW default 0/0 rewrites the trash page
            with itself — a bit-exact no-op)."""
            caches = copy_paged_cache_page(caches, cow_src, cow_dst)
            logits, one, _ = prefill(params, cfg, suffix,
                                     logits_index=suffix_len - 1,
                                     ctx_caches=caches,
                                     ctx_table=row[None],
                                     ctx_start=start)
            caches = scatter_prefill_paged_caches(caches, one, slot, row,
                                                  start)
            first = sample(logits[:, -1], rng)[0]
            return caches, first

        return prefill_into_slot

    def _build_wave_prefill(self):
        """The wave program: one compiled stage advances up to
        ``admit_group`` prefilling slots by one prompt chunk each —
        chunked prefill and grouped admission are the same dispatch at
        different (G, C) fill levels.  Built on the multi-position
        ``decode_step`` path (per-position causal masking +
        scatter-before-gather through the page table), so chunk rows are
        bit-identical to a monolithic prefill's; every composition of
        chunk width and lane occupancy reuses this one program because
        lengths, start positions, table rows and COW pairs are all data,
        not shape."""
        cfg, scfg = self.cfg, self.scfg
        sample = _slot_sampler(scfg)

        def wave(params, caches, tokens, lens, starts, rows, cow_src,
                 cow_dst, keys):
            """tokens: (G, C) prompt chunks, zero-padded; lens: (G,)
            real widths; starts: (G,) each chunk's global position;
            rows: (G, max_pages) page-table rows (all-trash for pad
            lanes, so their writes are harmless); cow_src/cow_dst: (G,)
            shared-tail duplication pairs applied before any write (the
            no-COW default 0/0 rewrites the trash page with itself);
            keys: (G, 2) per-request stream keys — the first-token draw
            folds in stream index 0, exactly the monolithic prefill's
            draw.  Returns the updated caches and each lane's sampled
            first token — meaningful only for lanes whose chunk
            completed the prompt."""
            caches = copy_paged_cache_page(caches, cow_src, cow_dst)
            logits, caches = decode_step(params, cfg, tokens, caches,
                                         starts, page_table=rows)
            last = jnp.take_along_axis(
                logits, (lens - 1)[:, None, None], axis=1)[:, 0]
            sub = jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys)
            return caches, sample(last, sub)

        return wave

    def _build_decode_chunk(self):
        cfg, scfg = self.cfg, self.scfg
        sample = _slot_sampler(scfg)
        max_pos = scfg.max_len - 1

        def chunk(params, caches, token, positions, active, remaining,
                  table, forced, forced_on, keys, counts):
            """Scan ``decode_chunk`` tokens; inactive slots are frozen
            (their rewrites land on already-written rows or on the trash
            page) and emit -1.  ``table`` is the (B, max_pages) page
            table.  ``forced`` /
            ``forced_on`` are (decode_chunk, B) teacher-forcing lanes:
            where ``forced_on`` a preempted request's stored token
            replaces the sampled one, replaying its stream verbatim so
            the rebuilt KV matches an uninterrupted run's.  ``keys`` /
            ``counts`` are the per-slot request keys and stream indices:
            step ``t`` of slot ``b`` draws with
            ``fold_in(keys[b], counts[b] + t)``, so replayed draws are
            discarded and fresh draws after a resume land on exactly
            the keys an uninterrupted run would have used — sampled
            streams are bit-stable under preemption."""
            def body(carry, xs):
                f_tok, f_on = xs
                caches, token, positions, active, remaining, counts = carry
                logits, caches = decode_step(params, cfg, token, caches,
                                             positions, page_table=table)
                nxt = sample(logits[:, -1], _fold_counts(keys, counts))
                nxt = jnp.where(f_on, f_tok, nxt)
                emitted = jnp.where(active, nxt, -1)
                remaining = remaining - active.astype(jnp.int32)
                alive = remaining > 0
                if scfg.eos_id >= 0:
                    alive = alive & (nxt != scfg.eos_id)
                new_active = active & alive
                positions = jnp.where(
                    active, jnp.minimum(positions + 1, max_pos), positions)
                token = jnp.where(active[:, None], nxt[:, None], token)
                carry = (caches, token, positions, new_active, remaining,
                         counts + 1)
                return carry, (emitted, active)

            init = (caches, token, positions, active, remaining, counts)
            carry, (toks, valid) = jax.lax.scan(
                body, init, (forced, forced_on), length=scfg.decode_chunk)
            return carry[:-1] + (toks, valid)

        return chunk

    def _build_draft(self):
        """The quantized draft program: a ``lax.scan`` of ``spec_k``
        decode steps under the *draft* config (nibble/quantized
        projections), proposing one token per step per slot.  Returns
        the drafted tokens and (temperature mode) each draw's full
        draft distribution — the verifier needs ``q(d)`` for rejection
        sampling.  Draft K/V writes land on rows the dense verify
        forward rewrites in the same round, so no quantized row ever
        survives into the attended history."""
        cfg, scfg = self._draft_cfg, self.scfg
        k = scfg.spec_k
        temp = scfg.temperature
        max_pos = scfg.max_len - 1

        def draft(params, caches, token, positions, active, table,
                  forced, forced_on, keys, counts):
            """token: (B, 1) last emitted per slot; positions: (B,) its
            row; forced/forced_on: (spec_k, B) replay lanes (a resumed
            request's committed tokens are re-proposed verbatim and
            force-accepted in verify); keys/counts: per-slot stream
            keys + the stream index of each slot's first draft."""
            def body(carry, xs):
                f_tok, f_on = xs
                caches, token, positions, counts = carry
                logits, caches = decode_step(params, cfg, token, caches,
                                             positions, page_table=table)
                lg = logits[:, -1].astype(jnp.float32)
                if temp > 0.0:
                    probs = jax.nn.softmax(lg / temp, axis=-1)
                    dkeys = jax.vmap(jax.random.fold_in, (0, None))(
                        _fold_counts(keys, counts), _TAG_DRAFT)
                    nxt = jax.vmap(jax.random.categorical)(
                        dkeys, lg / temp).astype(jnp.int32)
                else:
                    # greedy drafts carry no distribution; a width-1
                    # dummy keeps the verify signature uniform
                    probs = jnp.zeros((lg.shape[0], 1), jnp.float32)
                    nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                nxt = jnp.where(f_on, f_tok, nxt)
                positions = jnp.where(
                    active, jnp.minimum(positions + 1, max_pos), positions)
                token = jnp.where(active[:, None], nxt[:, None], token)
                return (caches, token, positions, counts + 1), (nxt, probs)

            init = (caches, token, positions, counts)
            (caches, _, _, _), (drafts, dprobs) = jax.lax.scan(
                body, init, (forced, forced_on), length=k)
            return caches, drafts, dprobs     # (k, B), (k, B, V or 1)

        return draft

    def _build_verify(self):
        """The dense verify program: ONE multi-token forward evaluates
        the last emitted token plus all ``spec_k`` drafts per slot
        (``decode_step`` with S = k+1 — the same multi-position
        machinery the prefill path uses, not a third program shape per
        request mix), rewriting rows ``start .. start+k`` with dense
        K/V and returning, per slot, the emission candidates and the
        per-draft acceptance mask.

        Greedy: draft j is accepted iff it equals the dense argmax at
        its position, so every accepted token — and the correction
        token emitted at the first mismatch — is *exactly* the token
        the non-spec dense engine would have produced (bit-match by
        construction).  Temperature > 0: standard rejection sampling —
        accept draft ``d`` with probability ``min(1, p(d)/q(d))``,
        resample rejections from ``normalize(max(p - q, 0))``, and draw
        a bonus token from the dense distribution when every draft
        survives; the emitted stream is distributed exactly as the
        dense model's.  All draws are index-derived (stream keys +
        tags), never split-chained.  Replayed (forced) drafts are
        force-accepted: they are committed history, not proposals."""
        cfg, scfg = self.cfg, self.scfg
        k = scfg.spec_k
        temp = scfg.temperature

        def verify(params, caches, token, drafts, start, table,
                   forced_on, dprobs, keys, counts):
            """token: (B, 1); drafts: (k, B) from the draft program;
            start: (B,) row of ``token``; forced_on: (k, B);
            dprobs: (k, B, V) draft distributions ((k, B, 1) dummy in
            greedy mode); counts: stream index of ``drafts[0]``."""
            tokens = jnp.concatenate([token, drafts.T], axis=1)  # (B,k+1)
            logits, caches = decode_step(params, cfg, tokens, caches,
                                         start, page_table=table)
            lg = logits.astype(jnp.float32)                   # (B,k+1,V)
            d = tokens[:, 1:]                                 # (B, k)
            f_on = forced_on.T                                # (B, k)
            if temp > 0.0:
                p = jax.nn.softmax(lg / temp, axis=-1)
                q = jnp.moveaxis(dprobs, 0, 1)                # (B, k, V)
                pd = jnp.take_along_axis(p[:, :-1], d[..., None],
                                         axis=-1)[..., 0]
                qd = jnp.take_along_axis(q, d[..., None], axis=-1)[..., 0]
                idx = counts[:, None] + jnp.arange(k)[None, :]
                step_keys = jax.vmap(lambda key, ii: jax.vmap(
                    lambda i: jax.random.fold_in(key, i))(ii))(keys, idx)
                u = jax.vmap(jax.vmap(lambda sk: jax.random.uniform(
                    jax.random.fold_in(sk, _TAG_ACCEPT), ())))(step_keys)
                accept = f_on | (u * qd <= pd)
                resid = jnp.maximum(p[:, :-1] - q, 0.0)
                rs = jnp.sum(resid, axis=-1, keepdims=True)
                resid = jnp.where(rs > 0, resid / rs, p[:, :-1])
                corr = jax.vmap(jax.vmap(
                    lambda sk, pr: jax.random.categorical(
                        jax.random.fold_in(sk, _TAG_RESAMPLE),
                        jnp.log(pr + 1e-30))))(step_keys, resid)
                bonus = jax.vmap(jax.random.categorical)(
                    _fold_counts(keys, counts + k), lg[:, -1] / temp)
                out = jnp.concatenate(
                    [jnp.where(accept, d, corr.astype(jnp.int32)),
                     bonus.astype(jnp.int32)[:, None]], axis=1)
            else:
                g = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # (B,k+1)
                accept = f_on | (d == g[:, :-1])
                out = jnp.concatenate([jnp.where(f_on, d, g[:, :-1]),
                                       g[:, -1:]], axis=1)
            return caches, out, accept

        return verify

    # ------------------------------------------------------------------
    # host-side state
    # ------------------------------------------------------------------

    def reset(self, rng=None) -> None:
        """Clear queue/slots (compiled functions and cache buffers are
        kept — stale cache rows are invisible: decode overwrites row
        ``p`` before any query can attend to it, and recycled pages are
        re-filled by their next owner's prefill)."""
        b = self.scfg.batch
        # index-derived RNG: one base key per run; request r's stream
        # key is fold_in(base, r.id) and every draw folds in the token's
        # stream index (plus a tag for spec sub-draws).  No split chain
        # to advance means no draw can shift with admission order,
        # batch composition or preemption — sampled streams are
        # bit-stable under evict-and-resume.
        self._base_key = rng if rng is not None else jax.random.PRNGKey(0)
        self._req_keys: dict[int, np.ndarray] = {}
        self._slot_keys = np.zeros((b, 2), np.uint32)
        self._queue = _PriorityQueue(self.scfg.priority_aging_s)
        self._slots: list[Request | None] = [None] * b
        self._token = np.zeros((b, 1), np.int32)
        self._positions = np.zeros((b,), np.int32)
        self._active = np.zeros((b,), bool)
        self._remaining = np.zeros((b,), np.int32)
        self._finished: dict[int, Request] = {}
        # teacher-forcing lanes for resumed requests: tokens generated
        # before a preemption, waiting to be replayed through the chunk
        self._slot_forced: list[list[int]] = [[] for _ in range(b)]
        self.preemptions = 0
        self._stat_samples = 0
        self._stat_running = 0
        self._stat_in_use = 0
        # speculative-decoding accounting (zero when spec_decode off):
        # proposed/accepted count *fresh* drafts only (replayed forced
        # tokens are committed history, force-accepted by contract, and
        # would inflate the acceptance rate), and only up to each
        # round's emission clamp (EOS / remaining budget) — positions a
        # round could never emit are not proposals.
        self.spec_rounds = 0
        self.spec_slot_rounds = 0
        self.spec_tokens = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_rollback_pages = 0
        # prefix-cache accounting: real tokens run through the prefill
        # stage (suffixes only, on a hit) vs prompt tokens served from
        # cached pages — the observable "prefilled only the suffix"
        self.prefill_tokens = 0
        self.cow_copies = 0
        self._prefix_hits = 0
        self._cached_prompt_tokens = 0
        self._total_prompt_tokens = 0
        # tail-latency accounting: wave/chunk dispatch counts, host-tier
        # swap traffic, and the decode steps a swap-in did NOT have to
        # replay (= generated rows restored by page copy)
        self.prefill_waves = 0
        self.decode_chunks = 0
        # scheduler counters at the boundaries the spans mark (``stats``)
        self.prefill_rows = 0
        self.decode_rows = 0
        self.queue_wait_s = 0.0
        self._step_num = 0
        self.swap_outs = 0
        self.swap_ins = 0
        self.replay_steps_saved = 0
        self.prefix_demotions = 0
        self.prefix_cold_hits = 0
        self.prefix_capacity_reclaims = 0
        # wave-mode per-slot prefill cursor: next prompt position to
        # run, -1 = not prefilling; _slot_cow holds each lane's pending
        # (cow_src, cow_dst) pair until its final chunk applies it
        self._prefill_next = np.full((b,), -1, np.int64)
        self._slot_cow: list[tuple[int, int]] = [(0, 0)] * b
        self.prefix_cache: PrefixCache | None = None
        self.host_pool: HostPagePool | None = None
        self.allocator = PageAllocator(self._num_pages, reserved=1)
        self.page_table = PageTable(b, self._max_pages, trash_page=0,
                                    num_pages=self._num_pages, reserved=1)
        self._slot_pages: list[list[int] | None] = [None] * b
        if self._swap:
            self.host_pool = HostPagePool(
                self.scfg.host_pages or 2 * self.allocator.capacity)
            # every host-tier extract/insert pads its page vector to one
            # fixed width (the per-slot maximum), so the eager
            # gather/scatter pair compiles exactly one shape — and that
            # compile is pre-paid here, on a trash-page round-trip,
            # instead of inside the serving loop at the first preemption
            self._swap_pad = self._max_pages
            warm = extract_cache_pages(self._caches, [0],
                                       pad_to=self._swap_pad)
            self._caches = insert_cache_pages(self._caches, [0], warm,
                                              pad_to=self._swap_pad)
            if self._cache_sh is not None:
                self._caches = jax.device_put(self._caches, self._cache_sh)
        if self.scfg.prefix_cache:
            self.prefix_cache = PrefixCache(self._page_size, self.allocator)
            if self.host_pool is not None:
                self.prefix_cache.attach_cold_tier(
                    self._demote_page, lambda hid: self.host_pool.free([hid]))

    @property
    def compile_counts(self) -> dict:
        """Compilations per stage — the refill-without-recompile claim
        is checkable: counts stay at 1 across arbitrary request mixes,
        page recyclings and preemptions (given a fixed ``prefill_len``
        slot budget).  Counted engine-side from distinct abstract call
        signatures (see ``_CountingJit``) — no jax-private probe.

        The pinned contract: a non-spec engine runs exactly
        ``{"prefill": 1, "decode_chunk": 1}`` once warm.  A spec engine
        replaces the chunk with the draft-side pair and runs exactly
        ``{"prefill": 1, "decode_chunk": 0, "draft": 1, "verify": 1}``
        — one quantized draft program, one dense multi-token verify
        program, and the chunk program never built or called.  Wave
        mode (``prefill_chunk``/``admit_group``) replaces the
        monolithic prefill with the wave program and runs exactly
        ``{"prefill": 0, "decode_chunk": 1, "prefill_chunk": 1}`` —
        every chunk width, lane occupancy and prefix-hit mix hits the
        same (G, C) signature.  Any other value is a recompile bug
        (``benchmarks/serve_bench.py`` raises on deviation)."""
        counts = {"prefill": (self._prefill_fn.compile_count
                              if self._prefill_fn is not None else 0),
                  "decode_chunk": (self._chunk_fn.compile_count
                                   if self._chunk_fn is not None else 0)}
        if self._wave:
            counts["prefill_chunk"] = self._wave_fn.compile_count
        if self._spec:
            counts["draft"] = self._draft_fn.compile_count
            counts["verify"] = self._verify_fn.compile_count
        return counts

    def stage_programs(self) -> dict:
        """The stage programs this engine actually built, as
        ``{stage_name: _CountingJit}`` — the entry point for
        ``repro.staticcheck``'s jaxpr layer, which re-lowers each
        recorded abstract signature and inspects the result.  Stages a
        mode never constructs (e.g. ``decode_chunk`` under spec
        decoding) are absent, mirroring ``compile_counts``."""
        stages = {}
        if self._prefill_fn is not None:
            stages["prefill"] = self._prefill_fn
        if self._wave_fn is not None:
            stages["prefill_chunk"] = self._wave_fn
        if self._chunk_fn is not None:
            stages["decode_chunk"] = self._chunk_fn
        if self._spec:
            stages["draft"] = self._draft_fn
            stages["verify"] = self._verify_fn
        return stages

    @property
    def stats(self) -> dict:
        """Scheduling counters for the run since the last ``reset``:
        ``preemptions`` (evict-and-resume events), ``occupancy`` (mean
        fraction of allocatable pool pages in use, sampled at each
        decode chunk), ``concurrency`` (mean admitted requests per
        chunk) and ``pool_pages`` (device pool size)."""
        n = max(1, self._stat_samples)
        return {"preemptions": self.preemptions,
                "occupancy": (self._stat_in_use
                              / (n * self.allocator.capacity)),
                "concurrency": self._stat_running / n,
                "pool_pages": self.allocator.num_pages,
                # prefix-cache counters (zero / empty without the cache):
                # hit_rate = prompt tokens served from cached pages over
                # all prompt tokens admitted; prefill_tokens = real
                # tokens actually run through the prefill stage
                "prefix_hits": self._prefix_hits,
                "prefix_hit_rate": (self._cached_prompt_tokens
                                    / max(1, self._total_prompt_tokens)),
                "prefill_tokens": self.prefill_tokens,
                "cow_copies": self.cow_copies,
                "prefix_pages": (len(self.prefix_cache)
                                 if self.prefix_cache is not None else 0),
                # speculative decoding (zeros with spec_decode off):
                # acceptance_rate = fresh drafts accepted / proposed;
                # tokens_per_step = tokens emitted per *sequence* per
                # draft+verify round (per slot-round, so it is
                # comparable to tools/spec_report's per-sequence
                # estimator; > 1 means each dense forward emitted more
                # than one token for that sequence)
                "spec_rounds": self.spec_rounds,
                "acceptance_rate": (self.spec_accepted
                                    / max(1, self.spec_proposed)),
                "tokens_per_step": (self.spec_tokens
                                    / max(1, self.spec_slot_rounds)),
                "spec_rollback_pages": self.spec_rollback_pages,
                # tail-latency counters: prefill_waves/decode_chunks =
                # program dispatches per stage; swap_out/swap_in =
                # host-tier page-swap events; replay_steps_saved =
                # decode rows restored by page copy instead of replay;
                # prefix_cold_* = cold-tier demotions and promoted-hit
                # pages (both 0 with the mechanisms off)
                "prefill_waves": self.prefill_waves,
                "decode_chunks": self.decode_chunks,
                # scheduler counters (see ``spans``): prefill_rows =
                # rows the prefill program dispatched, padding included;
                # decode_rows = active slot-steps decoded; queue_wait_s
                # = summed wait of fresh requests from due time to
                # placement
                "prefill_rows": self.prefill_rows,
                "decode_rows": self.decode_rows,
                "queue_wait_s": self.queue_wait_s,
                "swap_out": self.swap_outs,
                "swap_in": self.swap_ins,
                "replay_steps_saved": self.replay_steps_saved,
                "host_pages": (self.host_pool.capacity
                               if self.host_pool is not None else 0),
                "prefix_cold_pages": (self.prefix_cache.cold_size
                                      if self.prefix_cache is not None
                                      else 0),
                "prefix_cold_hits": self.prefix_cold_hits,
                "prefix_demotions": self.prefix_demotions}

    def running(self) -> list[Request]:
        """The requests in slots: decoding, or (wave mode) prefilling."""
        return [r for r in self._slots if r is not None]

    @property
    def cache_token_bytes(self) -> int:
        """KV-cache bytes per cached token, summed over every layer's
        sequence-axis leaves (scales and block stacking included) —
        multiply by a request's ``cache_rows`` for its HBM footprint."""
        rows = self._num_pages * self._page_size
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self._caches)[0]:
            key = path[-1].key if hasattr(path[-1], "key") else None
            if key in _SEQ_CACHE_KEYS:
                total += leaf.size * leaf.dtype.itemsize
        return total // rows

    def _pages_for(self, req: Request) -> int:
        """Worst-case page count for a request: prompt rows plus one row
        per decode step except the last token (which is sampled but
        never written back)."""
        rows = len(req.prompt) + req.max_new_tokens - 1
        return pages_needed(rows, self._page_size)

    def _alloc_pages_for(self, req: Request) -> int:
        """Pages booked at admission: the worst case in reserve mode;
        the prompt pages plus the first decode page in incremental mode
        (later pages arrive via per-chunk top-up — resumed requests
        regrow the same way while their tokens replay).  A swapped-out
        request restores ``swap_rows`` live rows by page copy and then
        writes its next decode row, so incremental mode books exactly
        those; reserve mode keeps the worst case, which covers the
        swapped rows by construction (they were live under the same
        booking before eviction)."""
        if not self._incremental:
            return self._pages_for(req)
        if req.swap_pages is not None:
            return pages_needed(req.swap_rows + 1, self._page_size)
        rows = len(req.prompt)
        if req.max_new_tokens > 1:
            rows += 1                 # first decode write lands at row p_len
        return pages_needed(rows, self._page_size)

    # ------------------------------------------------------------------
    # host cold tier (swap_mode="host")
    # ------------------------------------------------------------------

    def _demote_page(self, page: int) -> int | None:
        """Prefix-cache demotion hook: copy one reclaimed device page
        into a fresh host page, returning its id (``None`` when the
        host pool is full — the caller then evicts its own oldest cold
        entry and retries, or drops the chunk outright)."""
        hids = self.host_pool.alloc(1)
        if hids is None:
            return None
        self.host_pool.store(
            hids[0], extract_cache_pages(self._caches, [page],
                                         pad_to=self._swap_pad)[0])
        self.prefix_demotions += 1
        return hids[0]

    def _promote_cold(self, keys: list, pages: list) -> None:
        """Load a run of cold prefix chunks back into freshly allocated
        device pages (which admission has already mapped behind the hot
        prefix, so global row order is preserved) and insert them into
        the hot index under their original chain keys."""
        hids = self.prefix_cache.pop_cold(keys)
        payloads = [self.host_pool.load(h) for h in hids]
        self._caches = insert_cache_pages(self._caches, pages, payloads,
                                          pad_to=self._swap_pad)
        if self._cache_sh is not None:
            # the eager scatter may drop the committed sharding; re-pin
            # before the next donating dispatch sees a layout mismatch
            self._caches = jax.device_put(self._caches, self._cache_sh)
        self.host_pool.free(hids)
        self.prefix_cold_hits += len(pages)

    def _prefix_insert(self, keys: list, pages: list) -> None:
        """Index a prompt's chunk chain, then enforce the optional
        ``prefix_cache_pages`` capacity cap: reclaim (LRU leaf-first,
        demoting to the cold tier when attached) down to the budget.
        Best-effort — pages still mapped by live slots are pinned and
        may hold the index above the cap until their slot finishes."""
        self.prefix_cache.insert(keys, pages)
        cap = self.scfg.prefix_cache_pages
        if cap and len(self.prefix_cache) > cap:
            self.prefix_capacity_reclaims += self.prefix_cache.reclaim(
                len(self.prefix_cache) - cap)

    def validate(self, prompt, max_new_tokens: int):
        """Submit-time validation, shared with the router (which must
        reject an unserveable request at *its* front door rather than
        crash a replica at placement): returns the canonicalized
        ``(prompt, clamped_new_tokens, truncated)`` triple or raises."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        scfg = self.scfg
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        if prompt.size == 0 or prompt.size >= scfg.max_len:
            raise ValueError(f"prompt length {prompt.size} must be in "
                             f"[1, max_len={scfg.max_len})")
        if scfg.prefill_len and prompt.size > scfg.prefill_len \
                and not self._has_mamba:
            raise ValueError(f"prompt length {prompt.size} exceeds the "
                             f"slot budget prefill_len={scfg.prefill_len}")
        budget = scfg.max_len - prompt.size
        truncated = max_new_tokens > budget
        clamped = min(max_new_tokens, budget)
        need = pages_needed(prompt.size + clamped - 1, self._page_size)
        if need > self.allocator.capacity:
            raise ValueError(
                f"request needs {need} pages but the pool capacity is "
                f"{self.allocator.capacity}; raise num_pages or shorten "
                f"the request")
        return prompt, clamped, truncated

    def submit(self, prompt, max_new_tokens: int, arrival: float = 0.0,
               priority: int = 0) -> int:
        """Queue one request; returns its id.  ``arrival`` (seconds from
        ``run()`` start) models staggered workloads — the request is not
        admitted to a slot before its arrival time.  ``priority`` orders
        admission (higher first; see ``ServeConfig.priority_aging_s``)
        and preemption (a strictly-higher-priority arrival may evict a
        running slot).  A ``max_new_tokens`` that cannot fit the
        ``max_len`` budget is clamped and flagged on the returned
        request (``Request.truncated``) — explicit, never mistaken for
        an early EOS."""
        prompt, clamped, truncated = self.validate(prompt, max_new_tokens)
        req = Request(id=self._next_id, prompt=prompt,
                      max_new_tokens=clamped,
                      arrival=arrival, priority=priority,
                      truncated=truncated)
        self._next_id += 1
        self._queue.push(req)
        return req.id

    # ------------------------------------------------------------------
    # scheduling loop
    # ------------------------------------------------------------------

    def _prefix_plan(self, req: Request):
        """(chunk_keys, shared_pages, cow_src, start, n_cold) for the
        longest usable cached prefix of ``req.prompt``.  Read-only (no
        refs taken, no promotions): ``_can_admit`` probes it,
        ``_place`` re-derives it and acquires.  A fully covered prompt
        caps sharing at every page but keeps the tail as ``cow_src``:
        the last token must still run through the model for its logits,
        and its KV write needs a private copy-on-write page.  With a
        cold tier attached, ``n_cold`` chunks demoted to host pages
        extend the hot run and are promoted into fresh device pages at
        placement — except a cold *tail* chunk that would fully cover
        the prompt, which is cheaper to re-prefill than to promote and
        then COW-duplicate."""
        if req.chunk_keys is None:
            req.chunk_keys = self.prefix_cache.chunk_keys(req.prompt)
        keys = req.chunk_keys
        hits = self.prefix_cache.match(keys)
        p_len = int(req.prompt.size)
        if hits and len(hits) * self._page_size == p_len:
            return keys, hits[:-1], hits[-1], p_len - 1, 0
        n_cold = self.prefix_cache.match_cold(keys, len(hits))
        if n_cold and (len(hits) + n_cold) * self._page_size == p_len:
            n_cold -= 1
        start = (len(hits) + n_cold) * self._page_size
        return keys, hits, 0, start, n_cold

    def _admission_pages(self, req: Request) -> int:
        """Fresh pages admission must allocate: the booked count minus
        pages served read-only from the prefix cache.  A swapped-out
        request restores its own private pages — the prefix plan does
        not apply (its prompt pages come back by copy, not mapping)."""
        booked = self._alloc_pages_for(req)
        if self.prefix_cache is None or req.swap_pages is not None:
            return booked
        _, shared, _, _, _ = self._prefix_plan(req)
        return booked - len(shared)

    def _can_admit(self, req: Request) -> bool:
        """Admission backpressure: the pool must cover the request's
        booked pages (freed pages un-defer it later).  With the prefix
        cache, cached pages do not need allocating, and cold index
        entries are reclaimed (LRU, never this plan's own hits) before
        deferring."""
        need = self._admission_pages(req)
        if self.allocator.can_alloc(need):
            return True
        if self.prefix_cache is not None:
            if req.swap_pages is not None:
                keep = set()
            else:
                _, shared, cow_src, _, _ = self._prefix_plan(req)
                keep = set(shared) | ({cow_src} if cow_src else set())
            self.prefix_cache.reclaim(need - self.allocator.available,
                                      keep=keep)
        return self.allocator.can_alloc(need)

    def _pick_victim(self, now: float, below: int | None = None
                     ) -> int | None:
        """Slot of the weakest running request — lowest aging-adjusted
        effective priority, ties broken by evicting the youngest.  With
        ``below``, only slots *strictly* weaker qualify (admission-time
        preemption must not thrash equal-priority requests)."""
        best, best_key = None, None
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            eff = self._queue.effective(req, now)
            if below is not None and eff >= below:
                continue
            key = (eff, -req.arrival, -req.id)
            if best_key is None or key < best_key:
                best, best_key = slot, key
        return best

    def _evict(self, slot: int, now: float) -> None:
        """Preempt a running slot: return its pages to the pool and
        requeue the request carrying every token generated so far (the
        replay lane restores them on re-admission)."""
        req = self._slots[slot]
        with span(EVICT, req=req.id):
            self._evict_slot(slot, req)

    def _evict_slot(self, slot: int, req: Request) -> None:
        if self._slot_forced[slot]:
            # evicted mid-replay: splice the unreplayed tail back so the
            # requeued request carries the full generated stream
            req.tokens.extend(self._slot_forced[slot])
            self._slot_forced[slot] = []
        if self._wave and self._prefill_next[slot] >= 0:
            # evicted mid-prefill: nothing generated yet — drop the
            # partial chunk rows with the pages and restart the prompt
            # on re-admission
            self._prefill_next[slot] = -1
            self._slot_cow[slot] = (0, 0)
        elif self._swap and req.tokens and self._slot_pages[slot]:
            # host-tier swap: copy the live KV rows out so resume is an
            # O(pages) restore instead of an O(generated) replay.  The
            # slot's decode position — not len(tokens) — is the row
            # count: a mid-replay victim carries spliced tokens whose
            # rows were never rebuilt yet.  A full host pool silently
            # falls back to replay-resume.
            rows = int(self._positions[slot])
            hids = self.host_pool.alloc(
                pages_needed(rows, self._page_size))
            if hids is not None:
                payloads = extract_cache_pages(
                    self._caches, self._slot_pages[slot][:len(hids)],
                    pad_to=self._swap_pad)
                for h, pl in zip(hids, payloads):
                    self.host_pool.store(h, pl)
                req.swap_pages = hids
                req.swap_rows = rows
                self.swap_outs += 1
        if self._slot_pages[slot] is not None:
            self.allocator.free(self._slot_pages[slot])
            self._slot_pages[slot] = None
            self.page_table.clear(slot)
        self._slots[slot] = None
        self._active[slot] = False
        req.preemptions += 1
        self.preemptions += 1
        self._queue.push(req)

    def _evictable_pages(self, now: float, cutoff: int) -> int:
        """Pages the pool could recover by evicting every runner whose
        effective priority sits strictly below ``cutoff`` — the
        feasibility bound both preemption paths check before evicting
        anyone, so no runner is ever sacrificed for an arrival that
        still could not fit afterwards.

        Refcount-aware: a shared prefix page counts once, and only when
        every reference to it belongs to the would-be victims — plus,
        at most, the prefix index, whose pin the LRU reclaim can drop
        once the victims are gone (a holder that survives keeps the
        page off the free list, so such pages recover nothing).  Cold
        index entries reclaimable *today* are counted separately; the
        sets are disjoint (reclaimable-now pages have no slot holder),
        so no page is counted twice.  The index-pin credit cannot
        overcount either: a pinned chunk only becomes droppable when
        its whole descendant chain goes cold, and any surviving holder
        of a descendant chunk necessarily holds every ancestor too —
        which would show up in this very refcount check."""
        held: dict[int, int] = {}
        for s, r in enumerate(self._slots):
            if r is not None and self._queue.effective(r, now) < cutoff:
                for p in self._slot_pages[s] or ():
                    held[p] = held.get(p, 0) + 1
        pinned = (set(self.prefix_cache.pages)
                  if self.prefix_cache is not None else set())
        freed = sum(1 for p, c in held.items()
                    if self.allocator.refcount(p) == c + (p in pinned))
        cold = (self.prefix_cache.reclaimable()
                if self.prefix_cache is not None else 0)
        return self.allocator.available + freed + cold

    def _admit(self, now: float) -> None:
        """Admit arrived requests into free slots, best effective
        priority first; a strictly-higher-priority arrival blocked on a
        slot or on pages preempts the weakest runner(s)."""
        with span(ADMIT, queued=len(self._queue)):
            self._admit_arrived(now)

    def _admit_arrived(self, now: float) -> None:
        while True:
            free = next((s for s in range(self.scfg.batch)
                         if self._slots[s] is None), None)
            cand = self._queue.peek(now)
            if cand is None:
                return
            cutoff = self._queue.effective(cand, now)
            if free is None:
                # all slots busy: evict for the slot only if the
                # arrival's pages are also coverable, else the victim
                # would lose its slot to an inadmissible head-of-queue
                if (self._evictable_pages(now, cutoff)
                        < self._admission_pages(cand)):
                    return
                victim = self._pick_victim(now, below=cutoff)
                if victim is None:
                    return
                self._evict(victim, now)
                continue
            req = self._queue.pop(now, admit=self._can_admit)
            if req is None:
                # arrived but backpressured on pages: evict strictly
                # weaker runners until the pool covers it, else defer
                # (same feasibility bound before any eviction)
                if (self._evictable_pages(now, cutoff)
                        < self._admission_pages(cand)):
                    return
                while not self._can_admit(cand):
                    victim = self._pick_victim(now, below=cutoff)
                    if victim is None:
                        return
                    self._evict(victim, now)
                req = self._queue.pop(now, admit=self._can_admit)
                if req is None:
                    return
            self._place(free, req, now)

    def _prefix_place(self, slot: int, req: Request, key):
        """Prefix-cache admission: map the cached prefix read-only, book
        only the remaining pages, and run the uncached suffix through
        the shared compiled prefill (a miss is simply ``start == 0``).
        Afterwards the prompt's full page-aligned chunks — freshly
        written and mapped alike — are inserted into the index, which
        takes its own page references so they outlive this request.
        Returns the first-token logits sample."""
        p_len = int(req.prompt.size)
        keys, shared, cow_src, start, n_cold = self._prefix_plan(req)
        shared = self.prefix_cache.acquire(keys[:len(shared)])
        fresh = self.allocator.alloc(self._alloc_pages_for(req)
                                     - len(shared))
        if fresh is None:             # _can_admit vouched for this plan
            raise RuntimeError("page pool changed between admission "
                               "check and placement")
        if n_cold:
            # promote the cold run into the first fresh pages — they
            # sit right behind the hot prefix in the table row, so the
            # restored rows land at their original global positions
            self._promote_cold(keys[len(shared):len(shared) + n_cold],
                               fresh[:n_cold])
        cow_dst = fresh[0] if cow_src else 0
        pages = shared + fresh
        self.page_table.assign(slot, pages, shared=set(shared))
        self._slot_pages[slot] = pages
        req.cache_rows = max(req.cache_rows,
                             len(pages) * self._page_size)
        sfx = req.prompt[start:]
        sfx_len = p_len - start
        # the splice buffer must span every key position [0, p_len):
        # cached rows occupy [0, start) and the fresh suffix lands at
        # [start, p_len), so without a fixed slot budget the buffer
        # pads to the FULL prompt length, not the suffix length (which
        # would roll the fresh keys off the end of a short buffer)
        pad_len = self._prefill_pad(p_len)
        padded = np.zeros((1, pad_len), np.int32)
        padded[0, :sfx_len] = sfx
        with span(UPLOAD):
            args = (jnp.asarray(padded), sfx_len, slot,
                    jnp.asarray(self.page_table.row(slot)), start,
                    cow_src, cow_dst,
                    jax.random.fold_in(jnp.asarray(key), 0))
        self._caches, first = self._prefill_fn(self.params, self._caches,
                                               *args)
        self._prefix_insert(keys, pages)
        self.prefill_tokens += sfx_len
        self.prefill_rows += pad_len
        self._cached_prompt_tokens += start
        self._prefix_hits += bool(shared or n_cold or cow_src)
        self.cow_copies += bool(cow_src)
        return first

    def _prefill_pad(self, p_len: int) -> int:
        """Rows the prefill program runs for a prompt of ``p_len``
        tokens: the slot budget, or the prompt itself where there is
        none (and always for mamba mixers, which end-padding would
        corrupt)."""
        if self._has_mamba or not self.scfg.prefill_len:
            return p_len
        return self.scfg.prefill_len

    def _place(self, slot: int, req: Request, now: float) -> None:
        """Prefill a request into a free slot.  Fresh requests sample
        their first token from the prefill logits; resumed requests
        (non-empty ``tokens``) reuse their stored first token and queue
        the rest on the slot's teacher-forcing lane, so the rebuilt KV
        — and, for greedy decode, every later token — bit-matches an
        uninterrupted run.  A fresh request's wait from its due time to
        here counts in ``queue_wait_s``."""
        if req.tokens:
            # a replay re-prefills the prompt; a swap-in, or a wave
            # lane, dispatches no prefill here
            rows = (0 if req.swap_pages is not None or self._wave
                    else self._prefill_pad(int(req.prompt.size)))
            with span(RESUME, req=req.id, rows=rows):
                self._place_in_slot(slot, req)
            return
        wait_s = time.perf_counter() - self._t0 - req.arrival
        # a wave lane evicted mid-prefill comes back with no tokens: its
        # wait was counted at its first placement
        if not req.preemptions:
            self.queue_wait_s += wait_s
        if self._wave:
            # pages only: the waves prefill it, each an engine.prefill
            self._place_in_slot(slot, req)
            return
        p_len = int(req.prompt.size)
        with span(PREFILL, req=req.id, tokens=p_len,
                  rows=self._prefill_pad(p_len),
                  queue_wait_ms=1e3 * wait_s):
            self._place_in_slot(slot, req)

    def _place_in_slot(self, slot: int, req: Request) -> None:
        p_len = int(req.prompt.size)
        resumed = bool(req.tokens)
        # index-derived stream key: the same request always gets the
        # same key, whether fresh or re-admitted after a preemption.
        # The prefill's first-token draw is stream index 0.
        key = self._req_keys.get(req.id)
        if key is None:
            key = np.asarray(jax.random.fold_in(self._base_key, req.id),
                             np.uint32)
            self._req_keys[req.id] = key
        self._slot_keys[slot] = key
        if req.swap_pages is not None:
            # O(pages) resume: restore the swapped rows by copy — no
            # prefill, no replay (the prompt tokens were counted at the
            # original admission)
            self._swap_in(slot, req)
            return
        self._total_prompt_tokens += p_len
        if self._wave:
            # wave mode: map pages now, then advance one chunk per
            # scheduler step through the shared wave program
            self._wave_admit(slot, req)
            return
        if self.prefix_cache is not None:
            first = self._prefix_place(slot, req, key)
        else:
            pad_len = self._prefill_pad(p_len)
            # tokens stay at pad_len (page-rounding them would feed extra
            # pad tokens through mamba mixers); the prefill stage
            # zero-grows the cache to whole pages instead
            pages = self.allocator.alloc(self._alloc_pages_for(req))
            self.page_table.assign(slot, pages)
            self._slot_pages[slot] = pages
            req.cache_rows = max(req.cache_rows,
                                 len(pages) * self._page_size)
            padded = np.zeros((1, pad_len), np.int32)
            padded[0, :p_len] = req.prompt
            self.prefill_tokens += p_len
            self.prefill_rows += pad_len
            with span(UPLOAD):
                args = (jnp.asarray(padded), p_len, slot,
                        jnp.asarray(self.page_table.row(slot)),
                        jax.random.fold_in(jnp.asarray(key), 0))
            self._caches, first = self._prefill_fn(
                self.params, self._caches, *args)
        if not resumed:
            with span(WAIT):
                jax.block_until_ready(first)
            with span(READBACK):
                tok = int(first)
        with span(EMIT):
            if resumed:
                tok = req.tokens[0]
                self._slot_forced[slot] = req.tokens[1:]
                req.tokens = [tok]
            else:
                self._slot_forced[slot] = []
                req.tokens.append(tok)
                req.t_first = time.perf_counter() - self._t0
                req.t_tokens.append(req.t_first)
            done = (req.max_new_tokens <= 1
                    or (self.scfg.eos_id >= 0
                        and tok == self.scfg.eos_id))
            if done:
                self._finish(req, slot)
            else:
                self._slots[slot] = req
                self._token[slot, 0] = tok
                self._positions[slot] = p_len
                self._active[slot] = True
                self._remaining[slot] = req.max_new_tokens - 1

    def _swap_in(self, slot: int, req: Request) -> None:
        """Resume a swapped-out request by page copy: restore its live
        KV rows from the host tier into freshly allocated device pages
        and re-point the slot's table row — O(pages) host↔device
        traffic in place of O(generated) replayed decode steps.  The
        restore is a bit-copy, so the resumed stream (greedy, sampled
        or speculative) continues exactly where it stopped; tokens
        emitted but never written back re-enter the teacher-forcing
        lane as usual."""
        p_len = int(req.prompt.size)
        pages = self.allocator.alloc(self._alloc_pages_for(req))
        if pages is None:             # _can_admit vouched for this plan
            raise RuntimeError("page pool changed between admission "
                               "check and placement")
        n = len(req.swap_pages)
        payloads = [self.host_pool.load(h) for h in req.swap_pages]
        self._caches = insert_cache_pages(self._caches, pages[:n],
                                          payloads, pad_to=self._swap_pad)
        if self._cache_sh is not None:
            # the eager scatter may drop the committed sharding; re-pin
            # before the next donating dispatch sees a layout mismatch
            self._caches = jax.device_put(self._caches, self._cache_sh)
        self.host_pool.free(req.swap_pages)
        req.swap_pages = None
        self.page_table.assign(slot, pages)
        self._slot_pages[slot] = pages
        req.cache_rows = max(req.cache_rows,
                             len(pages) * self._page_size)
        # rows [0, swap_rows) are restored; tokens past the last one
        # written back replay through the forced lane, and the stream
        # resumes at the position the eviction interrupted
        committed = req.swap_rows - p_len + 1
        self._slot_forced[slot] = list(req.tokens[committed:])
        req.tokens = req.tokens[:committed]
        self._slots[slot] = req
        self._token[slot, 0] = int(req.tokens[-1])
        self._positions[slot] = req.swap_rows
        self._active[slot] = True
        self._remaining[slot] = req.max_new_tokens - committed
        self.swap_ins += 1
        self.replay_steps_saved += req.swap_rows - p_len
        req.swap_rows = 0

    def _wave_admit(self, slot: int, req: Request) -> None:
        """Wave-mode admission: allocate and map the request's pages
        now, but run no model code — the slot parks as an inactive
        *prefilling* lane (``_prefill_next`` ≥ 0) and advances one
        prompt chunk per scheduler step through the shared wave
        program.  Frozen-slot safety: the lane's decode position parks
        at ``max_len - 1``, whose garbage rewrites land past every row
        a prompt chunk attends and are overwritten by the slot's own
        decode before they could ever be read."""
        p_len = int(req.prompt.size)
        cow = (0, 0)
        start = 0
        if self.prefix_cache is not None:
            keys, shared, cow_src, start, n_cold = self._prefix_plan(req)
            shared = self.prefix_cache.acquire(keys[:len(shared)])
            fresh = self.allocator.alloc(self._alloc_pages_for(req)
                                         - len(shared))
            if fresh is None:         # _can_admit vouched for this plan
                raise RuntimeError("page pool changed between admission "
                                   "check and placement")
            if n_cold:
                self._promote_cold(
                    keys[len(shared):len(shared) + n_cold],
                    fresh[:n_cold])
            if cow_src:
                cow = (cow_src, fresh[0])
            pages = shared + fresh
            self.page_table.assign(slot, pages, shared=set(shared))
            self._cached_prompt_tokens += start
            self._prefix_hits += bool(shared or n_cold or cow_src)
        else:
            pages = self.allocator.alloc(self._alloc_pages_for(req))
            if pages is None:
                raise RuntimeError("page pool changed between admission "
                                   "check and placement")
            self.page_table.assign(slot, pages)
        self._slot_pages[slot] = pages
        req.cache_rows = max(req.cache_rows,
                             len(pages) * self._page_size)
        self._slots[slot] = req
        self._prefill_next[slot] = start
        self._slot_cow[slot] = cow
        self._slot_forced[slot] = []
        self._token[slot, 0] = 0
        self._positions[slot] = self.scfg.max_len - 1
        self._active[slot] = False
        self._remaining[slot] = 0

    def _run_wave(self, now: float) -> None:
        """One wave: advance up to ``admit_group`` prefilling lanes by
        one prompt chunk each through the single compiled wave program.
        Pad lanes ride along as no-ops (all-trash table rows, so their
        writes are harmless); a lane whose chunk completes its prompt
        samples its first token and unfreezes into decode."""
        G, C = self._wave_group, self._wave_chunk
        lanes = [s for s in range(self.scfg.batch)
                 if self._prefill_next[s] >= 0][:G]
        real = [min(C, int(self._slots[s].prompt.size)
                    - int(self._prefill_next[s])) for s in lanes]
        with span(PREFILL, lanes=len(lanes), tokens=sum(real),
                  rows=G * C):
            self._wave_dispatch(lanes, real)

    def _wave_dispatch(self, lanes: list, real: list) -> None:
        G, C = self._wave_group, self._wave_chunk
        tokens = np.zeros((G, C), np.int32)
        lens = np.ones((G,), np.int32)
        starts = np.zeros((G,), np.int32)
        rows = np.zeros((G, self._max_pages), np.int32)
        cow_src = np.zeros((G,), np.int32)
        cow_dst = np.zeros((G,), np.int32)
        keys = np.zeros((G, 2), np.uint32)
        for i, s in enumerate(lanes):
            req = self._slots[s]
            st = int(self._prefill_next[s])
            p_len = int(req.prompt.size)
            n = real[i]
            tokens[i, :n] = req.prompt[st:st + n]
            lens[i] = n
            starts[i] = st
            rows[i] = self.page_table.row(s)
            cs, cd = self._slot_cow[s]
            if cs and st + n == p_len:
                # the COW pair applies with the final chunk — the only
                # one that writes into the duplicated tail page
                cow_src[i], cow_dst[i] = cs, cd
                self.cow_copies += 1
            keys[i] = self._slot_keys[s]
            self.prefill_tokens += n
        self.prefill_waves += 1
        self.prefill_rows += G * C
        with span(UPLOAD):
            args = tuple(map(jnp.asarray, (tokens, lens, starts, rows,
                                           cow_src, cow_dst, keys)))
        self._caches, first = self._wave_fn(self.params, self._caches,
                                            *args)
        with span(WAIT):
            jax.block_until_ready(first)
        with span(READBACK):
            first = np.asarray(first)
        with span(EMIT):
            for i, s in enumerate(lanes):
                req = self._slots[s]
                nxt = int(self._prefill_next[s]) + real[i]
                if nxt >= int(req.prompt.size):
                    self._wave_finish(s, req, int(first[i]))
                else:
                    self._prefill_next[s] = nxt

    def _wave_finish(self, slot: int, req: Request, first: int) -> None:
        """A lane's final chunk ran: index the prompt's pages (prefix
        cache), commit the first token and unfreeze the slot — the
        exact epilogue of a monolithic placement, shared verbatim so
        wave and monolithic admissions are indistinguishable
        downstream."""
        self._prefill_next[slot] = -1
        self._slot_cow[slot] = (0, 0)
        if self.prefix_cache is not None:
            self._prefix_insert(req.chunk_keys, self._slot_pages[slot])
        if req.tokens:                # resumed: replay, don't resample
            tok = req.tokens[0]
            self._slot_forced[slot] = req.tokens[1:]
            req.tokens = [tok]
        else:
            self._slot_forced[slot] = []
            tok = first
            req.tokens.append(tok)
            req.t_first = time.perf_counter() - self._t0
            req.t_tokens.append(req.t_first)
        done = (req.max_new_tokens <= 1
                or (self.scfg.eos_id >= 0 and tok == self.scfg.eos_id))
        if done:
            self._finish(req, slot)
            self._slots[slot] = None
        else:
            self._token[slot, 0] = tok
            self._positions[slot] = int(req.prompt.size)
            self._active[slot] = True
            self._remaining[slot] = req.max_new_tokens - 1

    def _finish(self, req: Request, slot: int | None) -> None:
        req.t_done = time.perf_counter() - self._t0
        self._finished[req.id] = req
        self._req_keys.pop(req.id, None)
        if slot is not None:
            self._slot_forced[slot] = []
        if slot is not None and self._slot_pages[slot] is not None:
            # recycle: the freed pages may be handed to the very next
            # admission; the departing slot's table row is re-pointed at
            # the trash page so its frozen idempotent decode writes
            # cannot touch the new owner.  In incremental mode an
            # early-EOS request held only its live-token pages, so the
            # unreached tail was never booked at all.
            self.allocator.free(self._slot_pages[slot])
            self._slot_pages[slot] = None
            self.page_table.clear(slot)

    def _top_up(self, now: float) -> None:
        """Incremental mode: before a chunk, grow any active slot whose
        writes would cross its allocated page boundary.  When the pool
        is dry, preempt the weakest runner — possibly the needy slot
        itself, which then resumes once pages free up."""
        # rows a slot may make LIVE this dispatch: decode_chunk steps,
        # or — spec mode — up to spec_k accepted drafts plus the bonus
        # token.  Spec writes past the accepted length land on trash
        # (unbooked table tail) and are rolled back, so booking only
        # covers acceptable rows.
        chunk_steps = (self.scfg.spec_k + 1 if self._spec
                       else self.scfg.decode_chunk)
        for slot in range(self.scfg.batch):
            req = self._slots[slot]
            if req is None or not self._active[slot]:
                continue
            steps = min(chunk_steps, int(self._remaining[slot]))
            need = pages_needed(int(self._positions[slot]) + steps,
                                self._page_size)
            while need > self.page_table.live_len(slot):
                deficit = need - self.page_table.live_len(slot)
                got = self.allocator.alloc(deficit)
                if got is not None:
                    self.page_table.extend(slot, got)
                    self._slot_pages[slot].extend(got)
                    req.cache_rows = max(
                        req.cache_rows,
                        len(self._slot_pages[slot]) * self._page_size)
                    break
                # cold prefix pages go before any runner is preempted
                if self.prefix_cache is not None and \
                        self.prefix_cache.reclaim(
                            deficit - self.allocator.available):
                    continue
                victim = self._pick_victim(now)
                # never None: this slot itself is running, hence a
                # candidate; self-eviction ends its top-up
                self._evict(victim, now)
                if victim == slot:
                    break

    def _run_chunk(self, now: float) -> None:
        if self._incremental:
            self._top_up(now)
            if not self._active.any():
                return               # top-up evicted the last runner
        nsteps = self.scfg.decode_chunk
        with span(DECODE, active=int(self._active.sum()),
                  steps=nsteps) as s:
            s.set_metadata(rows=self._decode_chunk(nsteps))

    def _decode_chunk(self, nsteps: int) -> int:
        """Dispatch one decode chunk, read it back and emit its tokens;
        returns the active slot-steps it ran."""
        b = self.scfg.batch
        forced = np.full((nsteps, b), -1, np.int32)
        forced_on = np.zeros((nsteps, b), bool)
        for slot in range(b):
            buf = self._slot_forced[slot]
            if buf and self._slots[slot] is not None:
                n = min(nsteps, len(buf))
                forced[:n, slot] = buf[:n]
                forced_on[:n, slot] = True
                del buf[:n]
        self._stat_samples += 1
        self._stat_running += sum(r is not None for r in self._slots)
        self.decode_chunks += 1
        self._stat_in_use += self.allocator.in_use
        counts = np.asarray(
            [len(r.tokens) if r is not None else 0 for r in self._slots],
            np.int32)
        with span(UPLOAD):
            args = tuple(map(jnp.asarray, (
                self._token, self._positions, self._active,
                self._remaining, self.page_table.asarray(), forced,
                forced_on, self._slot_keys, counts)))
        self._caches, *outs = self._chunk_fn(self.params, self._caches,
                                             *args)
        with span(WAIT):
            jax.block_until_ready(outs)
        with span(READBACK):
            # copies: host state is mutable
            token, positions, active, remaining, toks, valid = outs
            self._token = np.array(token)
            self._positions = np.array(positions)
            self._active = np.array(active)
            self._remaining = np.array(remaining)
            toks, valid = np.asarray(toks), np.asarray(valid)
        rows = int(valid.sum())
        self.decode_rows += rows
        with span(EMIT):
            tnow = time.perf_counter() - self._t0
            for slot, req in enumerate(self._slots):
                if req is None:
                    continue
                for t in range(toks.shape[0]):
                    if not valid[t, slot]:
                        break
                    tok = int(toks[t, slot])
                    req.tokens.append(tok)
                    if len(req.t_tokens) < len(req.tokens):
                        # replayed tokens keep their original stamps
                        req.t_tokens.append(tnow)
                    if (len(req.tokens) >= req.max_new_tokens
                            or (self.scfg.eos_id >= 0
                                and tok == self.scfg.eos_id)):
                        self._finish(req, slot)
                        self._slots[slot] = None
                        break
        return rows

    def _spec_rollback(self, slot: int) -> None:
        """Roll a slot's rejected draft tail back as a page-table
        operation: truncate the live prefix to the pages its *accepted*
        rows need and return the tail pages to the allocator.  No cache
        row is copied or zeroed — the junk rows in the freed pages are
        exactly the idempotent writes the trash-page invariant already
        tolerates, and the truncated table entries point at the trash
        page so the freed pages' next owner is never aliased.  Only
        incremental mode books pages past the live prefix mid-stream;
        reserve-mode bookings are worst-case by contract and stay put.

        Shared prefix pages are unreachable by construction: the keep
        count covers at least the prompt rows plus one emitted token,
        which is strictly more pages than the prompt's shared full
        chunks."""
        if not self._incremental:
            return
        keep = pages_needed(int(self._positions[slot]), self._page_size)
        removed = self.page_table.truncate(slot, keep)
        if removed:
            self.allocator.free(removed)
            del self._slot_pages[slot][keep:]
            self.spec_rollback_pages += len(removed)

    def _run_spec_round(self, now: float) -> None:
        """One speculation round: quantized draft of ``spec_k`` tokens
        per slot, ONE dense multi-token verify forward over all draft
        positions, then a host-side walk that emits the accepted prefix
        (plus the correction or bonus token) and rolls back whatever
        the round over-wrote.  Greedy rounds emit exactly the stream
        the non-spec dense engine would."""
        if self._incremental:
            self._top_up(now)
            if not self._active.any():
                return               # top-up evicted the last runner
        k = self.scfg.spec_k
        active = int(self._active.sum())
        with span(DECODE, active=active, steps=k, rows=active * k):
            self._spec_round(k)
        self.decode_rows += active * k

    def _spec_round(self, k: int) -> None:
        b = self.scfg.batch
        forced = np.full((k, b), -1, np.int32)
        forced_on = np.zeros((k, b), bool)
        for slot in range(b):
            buf = self._slot_forced[slot]
            if buf and self._slots[slot] is not None:
                n = min(k, len(buf))
                forced[:n, slot] = buf[:n]
                forced_on[:n, slot] = True
                del buf[:n]
        self._stat_samples += 1
        self._stat_running += sum(r is not None for r in self._slots)
        self._stat_in_use += self.allocator.in_use
        counts = np.asarray(
            [len(r.tokens) if r is not None else 0 for r in self._slots],
            np.int32)
        start = self._positions.copy()
        with span(UPLOAD):
            keys = jnp.asarray(self._slot_keys)
            counts_j = jnp.asarray(counts)
            table = jnp.asarray(self.page_table.asarray())
            f_on = jnp.asarray(forced_on)
            token = jnp.asarray(self._token)
            start_j = jnp.asarray(start)
            active_j = jnp.asarray(self._active)
            forced_j = jnp.asarray(forced)
        # draft → verify stay device-side: the drafted tokens and their
        # distributions flow straight into the verify dispatch
        self._caches, drafts, dprobs = self._draft_fn(
            self.params, self._caches, token, start_j, active_j, table,
            forced_j, f_on, keys, counts_j)
        self._caches, out, accept = self._verify_fn(
            self.params, self._caches, token, drafts, start_j, table,
            f_on, dprobs, keys, counts_j)
        with span(WAIT):
            jax.block_until_ready((out, accept))
        with span(READBACK):
            out = np.asarray(out)        # (B, k+1) emission candidates
            accept = np.asarray(accept)  # (B, k) per-draft verdicts
        with span(EMIT):
            self._spec_emit(k, start, forced_on, out, accept)

    def _spec_emit(self, k: int, start, forced_on, out, accept) -> None:
        """The emission walk of one speculation round (see
        ``_run_spec_round``)."""
        b = self.scfg.batch
        self.spec_rounds += 1
        tnow = time.perf_counter() - self._t0
        eos = self.scfg.eos_id
        for slot in range(b):
            req = self._slots[slot]
            if req is None or not self._active[slot]:
                continue
            self.spec_slot_rounds += 1
            r = int(self._remaining[slot])
            e = 0
            # a replay longer than k spills into the next round: while
            # committed history remains buffered, the fresh bonus token
            # must NOT be emitted — it would splice a new token into a
            # stream the client has already seen
            more_forced = bool(self._slot_forced[slot])
            # emission walk: position j emits the accepted draft or the
            # correction token; the bonus position (j == k) is only
            # reached when every draft survived.  Positions past the
            # remaining budget or an EOS are never proposals.
            for j in range(k + 1):
                if j == k and more_forced:
                    break
                tok = int(out[slot, j])
                req.tokens.append(tok)
                if len(req.t_tokens) < len(req.tokens):
                    # replayed tokens keep their original stamps
                    req.t_tokens.append(tnow)
                e += 1
                r -= 1
                self.spec_tokens += 1
                if j < k and not forced_on[j, slot]:
                    self.spec_proposed += 1
                    if accept[slot, j]:
                        self.spec_accepted += 1
                if (r <= 0 or (eos >= 0 and tok == eos) or j == k
                        or not accept[slot, j]):
                    break
            self._positions[slot] = int(start[slot]) + e
            self._remaining[slot] = r
            self._token[slot, 0] = int(req.tokens[-1])
            if (len(req.tokens) >= req.max_new_tokens
                    or (eos >= 0 and int(req.tokens[-1]) == eos)):
                self._finish(req, slot)
                self._slots[slot] = None
                self._active[slot] = False
            else:
                self._spec_rollback(slot)

    def start(self, t0: float | None = None) -> None:
        """Anchor the run clock (arrivals and latency stamps are
        relative to it).  The router starts every replica on one shared
        ``t0`` so fleet-level percentiles are comparable."""
        self._t0 = time.perf_counter() if t0 is None else t0

    def step(self, wait: bool = True) -> bool:
        """One scheduler iteration: admit arrived requests, then run one
        decode chunk (or speculation round) if anything is active.
        Returns ``False`` once the engine is drained — no queued and no
        running requests.  ``wait=False`` skips the idle sleep before a
        future arrival (the router drives many replicas from one thread
        and must not block on the idlest one)."""
        if not (len(self._queue)
                or any(r is not None for r in self._slots)):
            return False
        self._step_num += 1
        with step_span(STEP, step_num=self._step_num):
            return self._step(wait)

    def _step(self, wait: bool) -> bool:
        now = time.perf_counter() - self._t0
        self._admit(now)
        # wave mode: slots mid-prefill are inactive but NOT idle — they
        # make progress through _run_wave below, so neither the idle
        # sleep nor the stall check may fire while any lane prefills
        prefilling = self._wave and bool((self._prefill_next >= 0).any())
        if not self._active.any() and not prefilling:
            if not len(self._queue):
                return False           # drained this iteration
            nxt = self._queue.next_arrival()
            wait_s = nxt - (time.perf_counter() - self._t0)
            if wait_s > 0:             # idle until the next arrival
                if wait:
                    with span(IDLE):
                        time.sleep(min(wait_s, 0.05))
                return True
            if nxt > now:
                # the request arrived *during* this iteration's _admit
                # window (arrival gating hid it from the `now` snapshot
                # _admit was given) — loop back and admit it with a
                # fresh clock, this is a healthy staggered workload,
                # not a stall
                return True
            # a request _admit could already see went unadmitted with
            # every slot idle.  An idle engine holds no pages, so this
            # is not backpressure — it is a page leak or an
            # unsatisfiable request, and overcommit/preemption make the
            # state reachable where it was once provably not.  Fail
            # loudly rather than spin on _admit forever.
            cached = (len(self.prefix_cache.pages)
                      if self.prefix_cache is not None else 0)
            detail = (f" ({self.allocator.in_use} pages still in use — "
                      f"{cached} pinned by the prefix index — "
                      f"{self.allocator.available} free of "
                      f"{self.allocator.capacity} allocatable)")
            if self.host_pool is not None:
                swapped = sum(1 for e in self._queue._heap
                              if e[3].swap_pages is not None)
                detail += (f" [host tier: {self.host_pool.in_use}/"
                           f"{self.host_pool.capacity} pages held, "
                           f"{swapped} swapped request(s) queued]")
            raise RuntimeError(
                f"serve scheduler stalled: {len(self._queue)} "
                f"arrived request(s) cannot be admitted with "
                f"all slots idle{detail}")
        now = time.perf_counter() - self._t0
        if prefilling:
            self._run_wave(now)
        if self._active.any():
            if self._spec:
                self._run_spec_round(now)
            else:
                self._run_chunk(now)
        return True

    def drain(self) -> dict[int, Request]:
        """Hand over (and clear) the finished-request map."""
        out, self._finished = self._finished, {}
        return out

    def run(self) -> dict[int, Request]:
        """Drain the queue: admit → chunked decode → refill, until every
        submitted request has finished.  Returns {id: Request} with
        per-request timing (t_first / t_done relative to run start)."""
        self.start()
        while self.step():
            pass
        return self.drain()

    def release_prefix_cache(self) -> None:
        """Drop every page reference the prefix index holds (teardown /
        leak checks: after a drained engine releases the cache, the
        allocator must report ``in_use == 0``)."""
        if self.prefix_cache is not None:
            self.prefix_cache.drop()

    def leaked_pages(self) -> int:
        """Pages still held after a drained engine has released every
        legitimate holder (call ``release_prefix_cache`` first when the
        prefix index is on) — anything non-zero is a leak, on the
        device pool *or* the host cold tier (a drained engine has no
        swapped requests and no cold entries left to hold host pages)."""
        host = self.host_pool.in_use if self.host_pool is not None else 0
        return self.allocator.in_use + host

    # ------------------------------------------------------------------
    # batch convenience API (examples / tests)
    # ------------------------------------------------------------------

    def generate(self, prompts: jax.Array, n_new: int,
                 rng=None) -> jax.Array:
        """prompts: (B, S) int32 → (B, S + n_new) tokens.

        Uniform-workload wrapper over submit/run: B must equal the slot
        count and every request decodes exactly ``n_new`` tokens, so
        the output is rectangular (build the engine with the default
        ``eos_id=-1``; early EOS stops raise)."""
        prompts = np.asarray(prompts, np.int32)
        b, s = prompts.shape
        if b != self.scfg.batch:
            raise ValueError(f"prompts batch {b} != ServeConfig.batch "
                             f"{self.scfg.batch}")
        if s + n_new > self.scfg.max_len:
            raise ValueError(f"prompt_len {s} + n_new {n_new} exceeds "
                             f"max_len {self.scfg.max_len}")
        self.reset(rng=rng if rng is not None else jax.random.PRNGKey(0))
        ids = [self.submit(prompts[i], n_new) for i in range(b)]
        done = self.run()
        short = [i for i in ids if len(done[i].tokens) != n_new]
        if short:
            # the max_len pre-check above rules out submit-time
            # truncation, so a short ragged output here can only be an
            # early EOS stop — say which, instead of guessing
            if any(done[i].truncated for i in short):
                raise RuntimeError(
                    f"generate() needs rectangular output but request(s) "
                    f"{short} were truncated at the max_len="
                    f"{self.scfg.max_len} budget")
            raise RuntimeError(
                f"generate() needs rectangular output but request(s) "
                f"{short} stopped at eos_id={self.scfg.eos_id} before "
                f"emitting n_new={n_new} tokens; use submit()/run() for "
                f"ragged workloads or build the engine with eos_id=-1")
        gen = np.stack([np.asarray(done[i].tokens, np.int32) for i in ids])
        return jnp.concatenate([jnp.asarray(prompts), jnp.asarray(gen)],
                               axis=1)
