"""Serving engine: continuous batching over a paged, host-spillable KV pool.

The thesis' runtime loop, applied to inference serving:

* requests arrive with a prompt; **prefill** computes the prompt's KV in
  one pass and packs it into pool pages (``page_pack`` semantics), or,
  for cache layouts without a paged K/V pool, token by token;
* **decode** runs in lockstep over the active batch through the compiled
  paged-attention step; the page table handed to XLA names only resident
  frames — the engine (the "driver") resolves residency beforehand;
* when the frame pool is exhausted, pages of *waiting* sequences spill to
  host (swap-out); re-scheduling such a sequence **faults** its pages back
  in with Touch-Ahead block granularity — accounting via the calibrated
  cost model, data movement real.

Pinning baseline: ``pin_all=True`` sizes residency for the worst case and
refuses admission beyond it (the thesis' memory-utilization cost).

Host spans (``jax.profiler.TraceAnnotation``, recorded only while a
profiler trace runs, on the device trace's clock): ``serve.step`` around
each ``step_decode`` (``step``), inside it ``serve.admit``,
``serve.prefill`` (``req_id``, ``prompt_tokens``), ``serve.gather``,
``serve.dispatch`` (the step program and sampling), ``serve.scatter`` and
``serve.retire``, and ``serve.pager`` around every call into the KV pager
(``op``, ``req_id``, and ``pages`` faulted in by ``ensure_resident``).
"""

from __future__ import annotations

import ctypes
import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.api.policy import FaultPolicy
from repro.core.arbiter import ServiceClass
from repro.core.resolver import Strategy
from repro.memory.kv_cache import PagedKVManager
from repro.vmem import coerce_policy
from repro.models.config import ModelConfig
from repro.models.registry import model_for
from repro.serving.sampler import SamplerConfig, sample_token


@functools.partial(jax.jit, static_argnums=1)
def _step(params, cfg: ModelConfig, cache, tokens):
    """The compiled one-token step, shared by every engine on ``cfg``:
    decode at ``(max_batch, 1)``, and the token-by-token prefill of the
    cache layouts that ``_prefill`` does not cover at ``(1, 1)``, each
    shape compiled once per process."""
    return model_for(cfg).decode_step(params, cfg, cache, tokens)


@functools.partial(jax.jit, static_argnums=1)
def _prefill(params, cfg: ModelConfig, cache, tokens, length):
    """The compiled one-pass prefill, shared by every engine on ``cfg``:
    one program per page bucket of the padded prompt."""
    return model_for(cfg).prefill(params, cfg, cache, tokens, length)


# glibc ``mallopt`` parameters, and the largest trim threshold set so far
_M_TRIM_THRESHOLD, _M_MMAP_THRESHOLD = -1, -3
_MMAP_THRESHOLD = 32 << 20      # the highest glibc's moving threshold climbs
_trim_threshold = 0


def _keep_host_heap(step_bytes: int) -> None:
    """Fix the C heap's thresholds for a round trip of ``step_bytes`` a step.

    Each decode step converts the whole batch cache between device and
    numpy, in fresh host buffers.  By default glibc moves its mmap and trim
    thresholds after what the process freed before, so whether those
    buffers are reused or mapped and faulted in anew, and with it the step's
    time, followed the process's history (on a TPU v5e host, 270-480 ms a
    step at StarCoder2-3B's widths).  Fixed thresholds make it steady:
    buffers under 32 MB come from the heap, and the heap keeps a step's
    worth of freed memory instead of handing it back between steps.  Only
    ever raised; nothing where the C library has no ``mallopt``.
    """
    global _trim_threshold
    trim = max(2 * _MMAP_THRESHOLD, 1 << (step_bytes - 1).bit_length())
    mallopt = getattr(ctypes.CDLL(None), "mallopt", None)
    if mallopt is None or trim <= _trim_threshold:
        return
    mallopt(_M_MMAP_THRESHOLD, _MMAP_THRESHOLD)
    mallopt(_M_TRIM_THRESHOLD, trim)
    _trim_threshold = trim


@dataclasses.dataclass
class Request:
    req_id: int
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int = 16
    generated: Optional[list] = None
    done: bool = False


@dataclasses.dataclass
class EngineStats:
    prefills: int = 0
    prefill_tokens: int = 0         # prompt tokens whose K/V prefill wrote
    prefill_step_calls: int = 0     # batch-1 ``_step`` calls prefill made
    decode_steps: int = 0
    tokens_generated: int = 0
    spill_events: int = 0
    fault_page_ins: int = 0
    # bytes of every array the batch gather and scatter convert between
    # device and numpy, either way, counted per conversion from its size
    cache_host_bytes: int = 0


class ServingEngine:
    """Single-host engine over one model; batch size fixed per decode step."""

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 max_len: int = 256, pool_frames: Optional[int] = None,
                 strategy: Optional[Strategy] = None,
                 policy: Optional[FaultPolicy] = None,
                 pin_all: bool = False,
                 sampler: SamplerConfig = SamplerConfig()):
        self.cfg = cfg
        self.params = params
        self.model = model_for(cfg)
        self.max_batch = max_batch
        self.max_len = max_len
        self.sampler = sampler
        self.pin_all = pin_all
        # this engine is one tenant of the KV fabric: its FaultPolicy decides
        # how spilled pages fault back in (legacy ``strategy`` deprecated).
        # Serving is latency-class traffic: unless the caller pinned a
        # class, its fault-back-ins arbitrate ahead of BULK tenants when
        # the KV pool is backed by the fabric (RemoteFramePool).
        self.policy = coerce_policy("ServingEngine", policy, strategy)
        if self.policy.service_class is None:
            self.policy = dataclasses.replace(
                self.policy, service_class=ServiceClass.LATENCY)
        ps = cfg.kv_page_tokens
        pages_per_seq = -(-max_len // ps)
        n_frames = pool_frames or max_batch * pages_per_seq
        self.kv = PagedKVManager(n_frames, ps, pages_per_seq,
                                 policy=self.policy)
        self.stats = EngineStats()
        # accumulation cursor into the shared vmem PagingStats
        self._kv_spills_seen = 0
        # fixed (max_batch) decode shape; cache pools sized to the device
        # pool (shared across the batch via page table)
        self.cache = self.model.init_decode_cache(cfg, max_batch, max_len)
        # the gather and the scatter convert the batch cache six times a step
        _keep_host_heap(6 * sum(x.nbytes for x in
                                jax.tree_util.tree_leaves(self.cache)))
        # one prefill pass per prompt where the model has the program and
        # the cache holds paged K/V pools; each page bucket compiles here,
        # as set-up, so that no admission compiles
        self._one_pass = (hasattr(self.model, "prefill")
                          and "k_pool" in self.cache)
        if self._one_pass:
            for pages in range(1, -(-(max_len - 1) // ps) + 1):
                self._prefill_pass(np.zeros(pages * ps, np.int32))
        self._seq_caches: dict = {}
        self.queue: list[Request] = []
        self.active: list[Request] = []
        self.req_counter = 0

    # -------------------------------------------------------------- intake
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16) -> Request:
        self.req_counter += 1
        r = Request(self.req_counter, np.asarray(prompt, np.int32),
                    max_new_tokens, generated=[])
        self.queue.append(r)
        return r

    # --------------------------------------------------------------- pager
    def _pager(self, op: str, req_id: int, *args, **kwargs):
        """``self.kv.<op>(req_id, ...)`` under a ``serve.pager`` span."""
        with TraceAnnotation("serve.pager", op=op, req_id=req_id) as span:
            out = getattr(self.kv, op)(req_id, *args, **kwargs)
            if op == "ensure_resident":
                span.set_metadata(pages=out)
        return out

    # ------------------------------------------------------------- prefill
    def _admit(self) -> None:
        with TraceAnnotation("serve.admit"):
            while self.queue and len(self.active) < self.max_batch:
                r = self.queue.pop(0)
                need_pages = -(-(len(r.prompt) + r.max_new_tokens)
                               // self.kv.page_tokens)
                if self.pin_all and self.kv.frames_used + need_pages > \
                        self.kv.n_frames:
                    self.queue.insert(0, r)     # admission control: refuse
                    break
                self._pager("add_sequence", r.req_id)
                waiting = [q.req_id for q in self.queue
                           if q.req_id in self.kv.seq_spaces]
                self._pager("append_tokens", r.req_id, len(r.prompt),
                            spill_candidates=waiting)
                self._prefill_sequence(r)
                self.active.append(r)
                self.stats.prefills += 1

    def _prefill_pass(self, head: np.ndarray):
        """A batch-1 decode cache holding ``head``'s K/V, from one prefill
        pass over it padded to whole pages (no pass for no tokens)."""
        cache = self.model.init_decode_cache(self.cfg, 1, self.max_len)
        if not len(head):
            return cache
        ps = self.cfg.kv_page_tokens
        tokens = np.zeros((1, -(-len(head) // ps) * ps), np.int32)
        tokens[0, :len(head)] = head
        return _prefill(self.params, self.cfg, cache, tokens,
                        np.int32(len(head)))

    def _prefill_sequence(self, r: Request) -> None:
        """Prefill of all but the last prompt token into a batch-1 decode
        cache: one pass on the paged layout, else token by token through
        the engine's compiled step at batch 1.

        The first decode step feeds the last prompt token and samples the
        first new one, so every token enters the cache exactly once.
        """
        with TraceAnnotation("serve.prefill", req_id=r.req_id,
                             prompt_tokens=len(r.prompt)):
            head = r.prompt[:-1]
            if self._one_pass:
                cache = self._prefill_pass(head)
            else:
                cache = self.model.init_decode_cache(self.cfg, 1,
                                                     self.max_len)
                for t in head:
                    _, cache = _step(self.params, self.cfg, cache,
                                     jnp.asarray([[t]], jnp.int32))
                self.stats.prefill_step_calls += len(head)
            self._seq_caches[r.req_id] = cache
            self.stats.prefill_tokens += len(head)

    # -------------------------------------------------------------- decode
    @staticmethod
    def _path_str(path) -> str:
        return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)

    def _to_host(self, x, copy: bool = False) -> np.ndarray:
        """``x`` as a numpy array (a writable copy with ``copy``), its
        bytes counted in ``cache_host_bytes``."""
        out = np.array(x) if copy else np.asarray(x)
        self.stats.cache_host_bytes += out.nbytes
        return out

    def _to_device(self, x: np.ndarray) -> jax.Array:
        """``x`` as a device array, its bytes counted in
        ``cache_host_bytes``."""
        self.stats.cache_host_bytes += x.nbytes
        return jnp.asarray(x)

    def _gather_batch_cache(self, batch: list[Request]):
        """Merge per-sequence caches into the fixed-batch decode cache.

        Convention: leaves whose path contains "pool" are frame pools
        (batch slot i owns pages [i·per_seq, (i+1)·per_seq)); "table"
        leaves are per-slot page tables; everything else carries the batch
        on axis 1 ((L, B, ...) stacked states) or axis 0 (lengths).
        """
        caches = [self._seq_caches[r.req_id] for r in batch]
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.cache)
        out = []
        for path, full in flat:
            name = self._path_str(path)
            arr = self._to_host(full, copy=True)
            for i in range(len(batch)):
                sub = caches[i]
                for p in path:
                    sub = sub[getattr(p, "key", getattr(p, "idx", None))]
                part = self._to_host(sub)
                if name == "lengths":
                    arr[i] = part[0]
                elif "pool" in name:
                    per_seq = part.shape[1]
                    arr[:, i * per_seq:(i + 1) * per_seq] = part
                elif "table" in name:
                    pass   # identity table already maps slot -> its range
                else:
                    arr[:, i] = part[:, 0]
            out.append(self._to_device(arr))
        return jax.tree_util.tree_unflatten(treedef, out)

    def step_decode(self) -> int:
        """One lockstep decode over all active sequences."""
        with TraceAnnotation("serve.step", step=self.stats.decode_steps + 1):
            self._admit()
            if not self.active:
                return 0
            batch = self.active[:self.max_batch]
            # residency: fault spilled pages back in before dispatch
            waiting = [q.req_id for q in self.queue
                       if q.req_id in self.kv.seq_spaces]
            for r in batch:
                self.stats.fault_page_ins += self._pager(
                    "ensure_resident", r.req_id, spill_candidates=waiting)
            # accumulate deltas from the shared PagingStats (the pager keeps
            # the source of truth; EngineStats no longer aliases it); a
            # negative delta means someone reset() the shared stats — the
            # post-reset total IS the delta then
            kv = self.kv.stats
            d_sp = kv.spills - self._kv_spills_seen
            self.stats.spill_events += d_sp if d_sp >= 0 else kv.spills
            self._kv_spills_seen = kv.spills

            tokens = np.zeros((self.max_batch, 1), np.int32)
            for i, r in enumerate(batch):
                last = r.generated[-1] if r.generated else r.prompt[-1]
                tokens[i, 0] = last
            with TraceAnnotation("serve.gather"):
                cache = self._gather_batch_cache(batch)
            with TraceAnnotation("serve.dispatch"):
                logits, cache = _step(self.params, self.cfg, cache,
                                      jnp.asarray(tokens))
                self.stats.decode_steps += 1
                key = jax.random.PRNGKey(self.stats.decode_steps)
                next_tokens = sample_token(logits[:, 0] if logits.ndim == 3
                                           else logits, self.sampler, key)
            # scatter results + updated caches back per sequence
            with TraceAnnotation("serve.scatter"):
                cache = jax.tree_util.tree_map(self._to_host, cache)
                for i, r in enumerate(batch):
                    tok = int(next_tokens[i])
                    r.generated.append(tok)
                    self._pager("append_tokens", r.req_id, 1)
                    self.stats.tokens_generated += 1
                    seq_cache = self._seq_caches[r.req_id]
                    flat, treedef = jax.tree_util.tree_flatten_with_path(
                        seq_cache)
                    out = []
                    for path, leaf in flat:
                        name = self._path_str(path)
                        big = cache
                        for p in path:
                            big = big[getattr(p, "key",
                                              getattr(p, "idx", None))]
                        if name == "lengths":
                            out.append(leaf + 1)
                        elif "pool" in name:
                            per_seq = self._to_host(leaf).shape[1]
                            out.append(self._to_device(
                                big[:, i * per_seq:(i + 1) * per_seq]))
                        elif "table" in name:
                            out.append(leaf)
                        else:
                            arr = self._to_host(leaf, copy=True)
                            arr[:, 0] = big[:, i]
                            out.append(self._to_device(arr))
                    self._seq_caches[r.req_id] = \
                        jax.tree_util.tree_unflatten(treedef, out)
                    if len(r.generated) >= r.max_new_tokens:
                        r.done = True
            with TraceAnnotation("serve.retire"):
                finished = [r for r in batch if r.done]
                for r in finished:
                    self.active.remove(r)
                    self._pager("free_sequence", r.req_id)
                    self._seq_caches.pop(r.req_id, None)
            return len(batch)

    def run_until_done(self, max_steps: int = 10_000) -> None:
        steps = 0
        while (self.queue or self.active) and steps < max_steps:
            if self.step_decode() == 0:
                break
            steps += 1
