"""Batched serving engine over packed-ternary weights.

The deployment story the paper targets: weights live in HBM at 1.6 bits each
(``quantize_for_serving``), prefill builds the KV/state caches, and the
decode loop streams packed weights through the dequant path every step —
memory-bound, which is exactly where the 10× weight-byte reduction pays.

The engine adds the serving substrate around the model's decode_step:
  * request batching with left-padded prompts of unequal length,
  * greedy / temperature / top-k sampling,
  * per-step token callbacks (streaming) and stop-token handling,
  * two batching disciplines: ``run`` (generational — the whole batch turns
    over at the pace of its slowest request; kept as a simple oracle and
    baseline) and ``serve`` (continuous — per-slot positions, finished slots
    refilled mid-flight from a FIFO queue via
    :class:`repro.serving.scheduler.ContinuousScheduler`).
"""

from __future__ import annotations

import itertools
import logging
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.decode import (
    cache_len,
    decode_step,
    init_cache,
    prefill,
    prefill_chunk as model_prefill_chunk,  # `prefill_chunk` is an engine kwarg
    prefill_chunks_of,
    supports_chunked_prefill,
)


logger = logging.getLogger(__name__)


@dataclass
class SamplerConfig:
    temperature: float = 0.0  # 0 → greedy
    top_k: int = 0
    seed: int = 0
    #: greedy selection via :func:`greedy_tokens` (bf16-canonicalized argmax)
    #: instead of raw f32 argmax.  The speculative path ALWAYS selects
    #: canonically (its free token, draft proposals, and verify predictions
    #: must agree across differently-compiled programs); set this on a
    #: non-speculative engine to make its greedy stream byte-comparable to a
    #: speculative one.  Off by default: raw argmax is the historical
    #: semantic, and the bf16 grid draws its own tie boundaries (a sharded
    #: run whose psum drift spans a grid edge can flip differently than raw).
    canonical_greedy: bool = False


def greedy_tokens(logits: jax.Array) -> jax.Array:
    """Canonical greedy selection: round logits to bf16, then argmax.

    Logits come off a bf16 matmul, so adjacent candidates routinely sit
    within one bf16 ulp of each other — and XLA compiles the *same* float
    math to slightly different last bits in different programs (jitted
    sched_step vs the fused speculative round vs op-by-op eager; measured
    ~3e-4 drift on this backend, ~50x below the bf16 grid at logit scale).
    Raw f32 argmax lets that sub-ulp drift flip near-tie tokens between
    programs, which would break the speculative path's byte-identity
    guarantee.  Rounding to bf16 first collapses sub-ulp drift back onto one
    grid point, and exact bf16 ties resolve to the lowest token id in every
    code path — so every greedy consumer in the speculative round (the
    sampler via ``canonical_greedy``, draft proposals, verify predictions)
    picks the same token for the same underlying distribution.
    """
    return jnp.argmax(logits.astype(jnp.bfloat16), axis=-1).astype(jnp.int32)


def sample_tokens(logits: jax.Array, cfg: SamplerConfig, key) -> jax.Array:
    if cfg.temperature <= 0.0:
        if cfg.canonical_greedy:
            return greedy_tokens(logits)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / cfg.temperature
    if cfg.top_k:
        kth = jax.lax.top_k(logits, cfg.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


#: process-wide monotonic request-id source (see ``Request.rid``)
_RID = itertools.count()


@dataclass
class Request:
    prompt: list[int]
    max_new_tokens: int = 32
    stop_token: int | None = None
    #: streaming callback, fired as ``on_token(request, token)`` per emitted
    #: token (overrides any scheduler-wide callback)
    on_token: Callable[["Request", int], None] | None = None
    out: list[int] = field(default_factory=list)
    done: bool = False
    #: traffic class this request belongs to (multi-tenant workload replay);
    #: None for direct API use — the engine never reads it, but scheduler
    #: stats and the load-generator's SLO analysis group by it
    tenant: str | None = None
    #: stable monotonically-assigned request id — the key for any per-request
    #: bookkeeping map (TTFT/TPOT/acceptance).  ``id(request)`` is NOT safe
    #: for that: CPython reuses object ids after GC, so a long-running server
    #: keyed on identity can silently merge two requests' stats.
    rid: int = field(default_factory=_RID.__next__)


#: token fed to dead/padding slots (any in-vocab id works; outputs of those
#: rows are never surfaced)
PAD_TOKEN = 1


class DecodeEngine:
    def __init__(self, params, cfg: ModelConfig, *, batch_size: int,
                 max_len: int, sampler: SamplerConfig | None = None,
                 matmul_policy: str | None = None, prefill_chunk: int = 32,
                 mesh=None, prefix_cache=False,
                 prefix_cache_mb: float = 64.0,
                 draft: tuple[Any, ModelConfig] | None = None,
                 spec_k: int = 4):
        """``matmul_policy`` overrides ``cfg.matmul_policy`` for every ternary
        projection this engine executes ("auto" | "prior" | "fixed:<kernel>",
        see :mod:`repro.kernels.dispatch`).  Kernel selection happens once,
        at trace time of the jitted prefill/decode step.

        ``prefill_chunk`` sets the admission chunk size: prompts are padded
        to a multiple of it and scanned chunk-by-chunk through one compiled
        trace (clamped to the ring length on windowed configs so a chunk
        never collides with itself).  Architectures without chunked-prefill
        support fall back to whole-prompt admission, which retraces per
        prompt length.

        ``mesh`` (a ``jax.sharding.Mesh`` with the repo's ``data``/``model``
        axes, see ``launch.mesh``) turns on sharded serving: packed weights
        are placed per the TP/EP rules in :mod:`repro.parallel.sharding`
        (``param_shardings``), the scheduler state's KV/state cache per
        ``cache_specs``, and the jitted prefill-chunk / admit-commit /
        sched-step entry points carry explicit in/out shardings so GSPMD
        partitions every step.  Kernel dispatch runs under a
        ``dispatch.shard_scope`` whose :class:`~repro.kernels.dispatch.ShardInfo`
        maps each matmul to its per-device shard — autotune-cache keys and
        prior scores are derived from the *local* problem.  The scheduling
        protocol is unchanged: a ``ContinuousScheduler`` drives a sharded
        engine exactly like a single-device one.

        ``prefix_cache`` turns on hashed shared-prefix KV reuse: pass True
        (a fresh :class:`repro.serving.prefix_cache.PrefixBlockStore` with a
        ``prefix_cache_mb`` byte budget) or a store instance to share across
        engines.  Admission then consults the store per prompt block
        (block = one ``prefill_chunk``), splices cached KV slabs instead of
        recomputing hit blocks, and publishes each freshly-computed full
        block.  Only effective on chunked-admission architectures — the
        whole-prompt fallback families carry recurrent state a KV slab
        cannot capture — and on windowed configs reuse depth is capped at
        the ring length (deeper blocks would be overwritten before the
        prompt tail attends them).

        ``draft`` = ``(draft_params, draft_cfg)`` turns on draft-and-verify
        speculative decoding on the continuous path: each scheduler step the
        (small, replicated) draft model proposes ``spec_k - 1`` greedy
        continuations of the target's free next token and the target scores
        all ``spec_k`` candidates in ONE batched ``verify_step`` forward;
        the accepted prefix is kept, the rejected suffix's KV/pos writes are
        rewound on both caches (``rollback_kv_window``).  Greedy streams are
        preserved exactly: every emitted token is, by construction, the
        target's own argmax — the draft only decides how many of them one
        step yields.  Requires temperature-0 sampling, a shared
        tokenizer/vocab, and chunked-prefill-capable architectures on both
        sides (the batched verify is the chunk forward); admission prefills
        the draft cache alongside the target's.  The generational ``run()``
        path ignores the draft."""
        if matmul_policy is not None:
            cfg = cfg.with_(matmul_policy=matmul_policy)
        self.cfg = cfg
        self.B = batch_size
        self.batch_size = batch_size  # ScheduleBackend protocol name
        self.max_len = max_len
        self.sampler = sampler or SamplerConfig()
        self.prefill_chunk = max(1, min(prefill_chunk,
                                        cache_len(cfg, max_len)))
        self.chunked_admission = supports_chunked_prefill(params, cfg)
        self._CL = cache_len(cfg, max_len)
        self.prefix_store = self._make_prefix_store(prefix_cache,
                                                    prefix_cache_mb)
        #: speculative decoding: 0 = off; >= 2 = candidates scored per
        #: verify step (1 free target token + spec_k - 1 drafted)
        self.spec_k = 0
        self.draft_params = None
        self.draft_cfg: ModelConfig | None = None
        if draft is not None:
            draft_params, draft_cfg = draft
            if matmul_policy is not None:
                draft_cfg = draft_cfg.with_(matmul_policy=matmul_policy)
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft/target tokenizer mismatch: draft "
                    f"{draft_cfg.name} has vocab_size {draft_cfg.vocab_size} "
                    f"but target {cfg.name} has {cfg.vocab_size}; "
                    f"speculative decoding compares token ids directly, so "
                    f"draft and target must share one tokenizer/vocab")
            if self.sampler.temperature > 0.0:
                raise ValueError(
                    f"speculative decoding preserves greedy streams only "
                    f"(temperature=0); got temperature="
                    f"{self.sampler.temperature}")
            if spec_k < 2:
                raise ValueError(
                    f"spec_k must be >= 2 (the target's free next token plus "
                    f"at least one drafted candidate); got {spec_k}")
            for side, c in (("target", cfg), ("draft", draft_cfg)):
                if spec_k > cache_len(c, max_len):
                    raise ValueError(
                        f"spec_k {spec_k} exceeds the {side} ring length "
                        f"{cache_len(c, max_len)}: one verify window would "
                        f"collide with itself in the KV ring")
            for side, pp, c in (("target", params, cfg),
                                ("draft", draft_params, draft_cfg)):
                if not supports_chunked_prefill(pp, c):
                    raise ValueError(
                        f"speculative decoding needs the batched verify "
                        f"forward (the chunked-prefill path), which the "
                        f"{side} architecture {c.name} does not support "
                        f"(block_pattern={c.block_pattern})")
            self.spec_k = spec_k
            self.draft_cfg = draft_cfg
            self.draft_params = draft_params
        self.mesh = mesh
        #: per-entry-point trace-time shard geometry (mesh mode only).  The
        #: batch divisor differs per entry: the batched decode step shards
        #: its M = B rows on the data axis, while admission prefills one
        #: request at a time (M = chunk length — sequence, not batch).
        self._shard_infos: dict[str, Any] = {}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.kernels.dispatch import ShardInfo
            from repro.parallel import sharding as sh

            axes = dict(zip(mesh.axis_names, mesh.devices.shape))
            model = axes.get("model", 1)
            data = axes.get("pod", 1) * axes.get("data", 1)
            heads = dict(wq=cfg.n_heads, wk=cfg.n_kv_heads)
            decode_info = ShardInfo(model=model, data=data, batch=data,
                                    n_heads=cfg.n_heads,
                                    n_kv_heads=cfg.n_kv_heads)
            admit_info = ShardInfo(model=model, data=data, batch=1,
                                   n_heads=cfg.n_heads,
                                   n_kv_heads=cfg.n_kv_heads)
            self._shard_infos = {
                "decode_step": decode_info, "sched_step": decode_info,
                "prefill": admit_info, "prefill_chunk": admit_info,
                "admit_commit": admit_info,
            }
            if self.spec_k:
                # the draft model runs replicated (model=1): its params and
                # cache are small by construction, and TP collectives on a
                # sub-billion-parameter draft would cost more than they save.
                # The verify half of spec_step is the TARGET forward and
                # keeps the decode-step TP geometry.
                self._shard_infos["spec_step"] = decode_info
                self._shard_infos["spec_draft"] = ShardInfo(
                    model=1, data=data, batch=data,
                    n_heads=self.draft_cfg.n_heads,
                    n_kv_heads=self.draft_cfg.n_kv_heads)
                self._shard_infos["draft_prefill_chunk"] = ShardInfo(
                    model=1, data=data, batch=1,
                    n_heads=self.draft_cfg.n_heads,
                    n_kv_heads=self.draft_cfg.n_kv_heads)
            self._psh = sh.param_shardings(params, mesh, heads=heads)
            params = jax.device_put(params, self._psh)
            repl = NamedSharding(mesh, PartitionSpec())
            if self.spec_k:
                self.draft_params = jax.device_put(self.draft_params, repl)
            state_sds = jax.eval_shape(self._state_template)
            self._state_sh = sh.to_shardings(
                sh.engine_state_specs(state_sds, mesh,
                                      kv_heads=cfg.n_kv_heads), mesh)
            cache1_sds = jax.eval_shape(
                lambda: init_cache(cfg, 1, self.max_len))
            self._cache1_sh = sh.to_shardings(
                sh.cache_specs(cache1_sds, mesh, kv_heads=cfg.n_kv_heads),
                mesh)

            def shardings(in_sh, out_sh):
                return {"in_shardings": in_sh, "out_shardings": out_sh}
        else:
            repl = None

            def shardings(in_sh, out_sh):
                return {}
        self.params = params
        #: jit traces per compiled entry point — the bucketed-admission
        #: guarantee is observable here: a mixed-length request stream keeps
        #: ``trace_counts["prefill_chunk"] == 1`` (one bucket shape)
        self.trace_counts: Counter[str] = Counter()
        # cache buffers are donated on every decode path (callers always
        # rebind the returned cache) so XLA updates KV in place
        self._step = jax.jit(
            self._counted("decode_step",
                          lambda p, c, t, i: decode_step(p, cfg, c, t, i)),
            donate_argnums=(1,))
        # the whole-prompt prefill also pins shardings in mesh mode: the
        # archs without chunked-prefill support admit through `_admit_whole`
        # → `_prefill`, and an unpinned jit would hand the commit a cache /
        # logits pair in whatever layout GSPMD propagated (observed: model-
        # sharded logits rejected by `_admit_commit_fn`'s replicated pin)
        self._prefill = jax.jit(
            self._counted("prefill",
                          lambda p, b: prefill(p, cfg, b, s_max=self.max_len)),
            **shardings(
                (getattr(self, "_psh", None), repl),
                (getattr(self, "_cache1_sh", None), repl)))
        # continuous-batching paths: the fixed-shape prefill chunk +
        # admission commit (bucketed path: one trace each; the whole-prompt
        # fallback reuses `_prefill` at B=1 — retraces per prompt length —
        # and the same commit), and the fused sample→mask→decode step.  The
        # live cache / state is donated — callers always replace it with the
        # returned value — so XLA updates the KV buffers in place instead of
        # copying the whole cache every token (same convention as
        # launch.dryrun).  In mesh mode these three entry points pin their
        # in/out shardings (params/cache/state per the sharding rules,
        # host-read outputs replicated) so the state's device layout is
        # stable step-over-step and donation aliases shard-for-shard.
        self._prefill_chunk_fn = jax.jit(
            self._counted("prefill_chunk",
                          lambda p, c, t, pos, take: model_prefill_chunk(
                              p, cfg, c, t, pos, take)),
            donate_argnums=(1,),
            **shardings(
                (getattr(self, "_psh", None), getattr(self, "_cache1_sh", None),
                 repl, repl, repl),
                (getattr(self, "_cache1_sh", None), repl)))
        # donate only the big state: the single-row chunk cache cannot alias
        # any [B, ...] output buffer, so donating it would just warn
        if self.spec_k:
            self._admit_commit_fn = jax.jit(
                self._counted("admit_commit", self._admit_commit_spec),
                donate_argnums=(0,),
                **shardings(
                    (getattr(self, "_state_sh", None),
                     getattr(self, "_cache1_sh", None), repl, repl, repl,
                     repl, repl, repl),
                    getattr(self, "_state_sh", None)))
        else:
            self._admit_commit_fn = jax.jit(
                self._counted("admit_commit", self._admit_commit),
                donate_argnums=(0,),
                **shardings(
                    (getattr(self, "_state_sh", None),
                     getattr(self, "_cache1_sh", None), repl, repl, repl,
                     repl, repl),
                    getattr(self, "_state_sh", None)))
        self._sched_step_fn = jax.jit(
            self._counted("sched_step", self._make_sched_step()),
            donate_argnums=(1,),
            **shardings(
                (getattr(self, "_psh", None), getattr(self, "_state_sh", None),
                 repl),
                (getattr(self, "_state_sh", None), repl, repl)))
        if self.spec_k:
            # the whole speculative round — draft-K scan, batched verify,
            # accept mask, rollback of both caches — is ONE jitted call per
            # scheduler step: K drafted positions plus K verified positions
            # ride a single host round-trip, so per-call overhead is paid
            # once per K-token window instead of once per token.  Draft
            # params/cache replicate; target entries keep their TP layout.
            dcfg = self.draft_cfg
            self._draft_prefill_chunk_fn = jax.jit(
                self._counted("draft_prefill_chunk",
                              lambda p, c, t, pos, take: model_prefill_chunk(
                                  p, dcfg, c, t, pos, take)),
                donate_argnums=(1,),
                **shardings((repl, repl, repl, repl, repl), (repl, repl)))
            self._spec_step_fn = jax.jit(
                self._counted("spec_step", self._make_spec_step()),
                donate_argnums=(2,),
                **shardings(
                    (getattr(self, "_psh", None), repl,
                     getattr(self, "_state_sh", None), repl),
                    (getattr(self, "_state_sh", None), repl, repl, repl,
                     repl)))
        if self.prefix_store is not None:
            # prefix-cache entry points: splice a stored KV slab into the
            # single-row admission cache / extract a just-prefilled block
            # for publication.  Both take the block start position as traced
            # int32 — one trace serves every block index — and both are
            # `_counted`, so the trace-honesty tests can assert cache HITS
            # mint no new prefill traces.  The slab layout matches
            # `sharding.block_slab_specs` in mesh mode (kv-head sharded
            # alongside the cache), so splicing stays resident per shard.
            from repro.models.decode import (extract_kv_blocks,
                                             splice_kv_blocks)

            C = self.prefill_chunk
            slab_sh = None
            if mesh is not None:
                from repro.parallel import sharding as sh

                slab_sds = jax.eval_shape(lambda: extract_kv_blocks(
                    cfg, init_cache(cfg, 1, self.max_len), 0, C))
                slab_sh = sh.to_shardings(
                    sh.block_slab_specs(slab_sds, mesh,
                                        kv_heads=cfg.n_kv_heads), mesh)
                self._slab_sh = slab_sh
            self._splice_block_fn = jax.jit(
                self._counted("splice_block",
                              lambda c, kb, vb, s: splice_kv_blocks(
                                  cfg, c, {"k": kb, "v": vb}, s)),
                donate_argnums=(0,),
                **shardings(
                    (getattr(self, "_cache1_sh", None),
                     slab_sh["k"] if slab_sh else None,
                     slab_sh["v"] if slab_sh else None, repl),
                    getattr(self, "_cache1_sh", None)))
            # the admission cache is NOT donated here: the caller keeps
            # prefilling through it after the extraction
            self._extract_block_fn = jax.jit(
                self._counted("extract_block",
                              lambda c, s: extract_kv_blocks(cfg, c, s, C)),
                **shardings(
                    (getattr(self, "_cache1_sh", None), repl), slab_sh))
        self._key = jax.random.PRNGKey(self.sampler.seed)

    def _make_prefix_store(self, prefix_cache, prefix_cache_mb: float):
        """Resolve the ``prefix_cache`` constructor arg into a
        :class:`~repro.serving.prefix_cache.PrefixBlockStore` (or None).
        The store's hash namespace binds the KV-producing geometry — config
        name, depth, kv-head shape — so slabs can never be replayed across
        engines whose caches they would not fit."""
        # identity checks, not truthiness: an EMPTY store instance is falsy
        # (len() == 0) but must still be wired in and validated
        if prefix_cache is None or prefix_cache is False:
            return None
        if not self.chunked_admission:
            logger.warning(
                "prefix cache requested but %s admits through whole-prompt "
                "fallback (no chunked prefill); prefix reuse disabled",
                self.cfg.name)
            return None
        from repro.serving.prefix_cache import PrefixBlockStore

        ns = (f"{self.cfg.name}:{self.cfg.n_layers}:{self.cfg.n_kv_heads}:"
              f"{self.cfg.head_dim}:{self.cfg.d_model}").encode()
        if prefix_cache is True:
            return PrefixBlockStore(
                self.prefill_chunk,
                max_bytes=max(1, int(prefix_cache_mb * (1 << 20))),
                namespace=ns)
        store = prefix_cache
        if store.block_tokens != self.prefill_chunk:
            raise ValueError(
                f"prefix store block size {store.block_tokens} != engine "
                f"prefill_chunk {self.prefill_chunk}: blocks are admission "
                f"chunks, the sizes must agree")
        if store.namespace != ns:
            raise ValueError(
                "prefix store namespace mismatch: the store was built for a "
                "different model geometry; sharing it would splice foreign "
                "KV slabs")
        return store

    def _counted(self, name: str, fn):
        """Wrap a to-be-jitted callable so each (re)trace bumps
        ``trace_counts[name]`` — cache hits never re-enter the wrapper.
        In mesh mode the trace also runs under the entry point's
        ``dispatch.shard_scope``, so every ternary-matmul selection inside
        keys on the per-device local problem."""
        info = self._shard_infos.get(name)

        def wrapped(*args):
            from repro.kernels.dispatch import shard_scope

            self.trace_counts[name] += 1
            with shard_scope(info):
                return fn(*args)
        return wrapped

    def matmul_shape_universe(self, *, include_prefill: bool = True
                              ) -> list[tuple[int, ...]]:
        """Every ternary-matmul problem this engine's steady-state serving
        paths dispatch: dense ``(M, K, N)`` triples — decode (``M = B``)
        plus, with ``include_prefill``, the admission-chunk bucket shape
        (``M = 1 · chunk`` — requests are prefilled one at a time, chunk by
        chunk) — and, for MoE configs, grouped ``(E, C, K, N)`` quads at the
        matching per-expert capacities (the expert stacks dispatch through
        ``grouped_ternary_matmul``).  Generational ``run()`` prefills at
        ``M = B · prompt_len`` for whatever prompt lengths arrive; those are
        workload-dependent and belong to ``benchmarks/autotune_sweep.py``,
        not the engine's fixed universe.

        With a draft model the universe also covers the speculative
        operating points: the target's K-token verify (``M = B · spec_k``
        through the ``spec_step`` geometry), the draft's per-step decode and
        admission-chunk problems (``model=1`` — the draft runs replicated,
        so its local problems are its global ones).

        In mesh mode the universe is **per-shard**: every problem is mapped
        through the entry point's ``ShardInfo`` (the same localization
        dispatch applies inside ``shard_scope``), so ``autotune_shapes``
        measures and records exactly the local problems each device runs."""
        from repro.models.decode import (layer_grouped_matmul_problems,
                                         layer_matmul_problems)

        shapes: set[tuple[int, ...]] = set()
        for c, bs, sl, entry in self._shape_sources(
                include_prefill=include_prefill):
            info = self._shard_infos.get(entry)
            for role, m, k, n in layer_matmul_problems(c, bs, seq_len=sl):
                if info is not None:
                    m, k, n = info.local_dense(role, m, k, n)
                shapes.add((m, k, n))
            for role, e, cap, k, n in layer_grouped_matmul_problems(
                    c, bs, seq_len=sl):
                if info is not None:
                    e, cap, k, n = info.local_grouped(role, e, cap, k, n)
                shapes.add((e, cap, k, n))
        return sorted(shapes)

    def _shape_sources(self, *, include_prefill: bool = True
                       ) -> list[tuple[ModelConfig, int, int, str]]:
        """The ``(cfg, batch_size, seq_len, entry_point)`` tuples whose
        matmul problems make up this engine's steady-state shape universe —
        target decode + admission chunk, and with a draft: target verify,
        draft decode, draft admission chunk."""
        sources = [(self.cfg, self.B, 1, "sched_step")]
        if include_prefill:
            sources.append((self.cfg, 1, self.prefill_chunk,
                            "prefill_chunk"))
        if self.spec_k:
            sources.append((self.cfg, self.B, self.spec_k, "spec_step"))
            sources.append((self.draft_cfg, self.B, 1, "spec_draft"))
            if include_prefill:
                sources.append((self.draft_cfg, 1, self.prefill_chunk,
                                "draft_prefill_chunk"))
        return sources

    def kernel_choices(self) -> dict[tuple[str, str, int, int, int], str]:
        """The kernel dispatch selects for every dense projection of this
        engine's steady-state traces: ``{(entry, role, M, K, N): name}``
        with global dims, resolved exactly as the traced matmuls resolve
        them — same policy, activation dtype, backend and shard scope."""
        from repro.kernels.dispatch import select_kernel, shard_scope
        from repro.models.decode import layer_matmul_problems

        out = {}
        for c, bs, sl, entry in self._shape_sources():
            act = "int8" if c.act_dtype == "int8" else c.dtype
            with shard_scope(self._shard_infos.get(entry)):
                for role, m, k, n in layer_matmul_problems(c, bs,
                                                           seq_len=sl):
                    out[(entry, role, m, k, n)] = select_kernel(
                        m, k, n, act, policy=c.matmul_policy,
                        mu=c.mu, role=role).name
        return out

    def autotune_shapes(self, *, include_prefill: bool = True,
                        **autotune_kw) -> dict:
        """Populate the dispatch autotune cache for this engine's per-step
        matmul shapes — decode *and* (by default) the prefill bucket shapes,
        dense and grouped-expert alike, so ``policy="auto"`` serving
        dispatches on measurements instead of always falling back to the
        analytical prior.  Call before the first `run`/`serve`."""
        from repro.kernels.dispatch import autotune, get_autotune_cache
        from repro.models.decode import (layer_grouped_matmul_problems,
                                         layer_matmul_problems)

        cache = get_autotune_cache()
        results = {}
        seen: set[tuple] = set()
        # iterate per source (not the merged universe): the act dtype the
        # dispatch keys on is per-config — a bf16-act draft and an int8-act
        # target may share a shape yet tune different kernel families
        for c, bs, sl, entry in self._shape_sources(
                include_prefill=include_prefill):
            info = self._shard_infos.get(entry)
            # under act_dtype="int8" every packed projection receives
            # pre-quantized int8 activations, so that is the dtype the
            # serving dispatch keys on (w2a8/tl2 become eligible)
            act = "int8" if c.act_dtype == "int8" else c.dtype
            probs: list[tuple[tuple[int, ...], int | None]] = []
            for role, m, k, n in layer_matmul_problems(c, bs, seq_len=sl):
                if info is not None:
                    m, k, n = info.local_dense(role, m, k, n)
                probs.append(((m, k, n), None))
            for role, e, cap, k, n in layer_grouped_matmul_problems(
                    c, bs, seq_len=sl):
                if info is not None:
                    e, cap, k, n = info.local_grouped(role, e, cap, k, n)
                probs.append(((e, cap, k, n), e))
            for shape, e in probs:
                if (shape, act) in seen:
                    continue
                seen.add((shape, act))
                if e is not None:
                    _, m, k, n = shape
                else:
                    m, k, n = shape
                results[shape] = autotune(m, k, n, act,
                                          mu=c.mu, cache=cache,
                                          save=False, e=e, **autotune_kw)
        cache.save()  # one write for the whole shape set
        return results

    def _stub_inputs(self, B: int) -> dict:
        extras: dict[str, Any] = {}
        if self.cfg.frontend == "audio_stub":
            extras["frames"] = jnp.zeros((B, self.cfg.enc_seq,
                                          self.cfg.d_model), jnp.bfloat16)
        if self.cfg.frontend == "vit_stub":
            extras["vision_embeds"] = jnp.zeros(
                (B, self.cfg.vision_tokens, self.cfg.d_model), jnp.bfloat16)
        return extras

    # ------------------------------------------------------------------
    # generational batching (baseline / oracle path)
    # ------------------------------------------------------------------

    def run(self, requests: list[Request]) -> list[Request]:
        """Run a batch of requests to completion (generational batching: all
        requests share one prompt length via left-trim and the batch turns
        over at the pace of its slowest request — use :meth:`serve` for
        continuous batching)."""
        if len(requests) > self.B:
            raise ValueError(
                f"got {len(requests)} requests for batch_size {self.B}; "
                "generational run() cannot queue — use serve() instead")
        reqs = list(requests) + [Request(prompt=[PAD_TOKEN], max_new_tokens=0)
                                 for _ in range(self.B - len(requests))]
        plen = max(len(r.prompt) for r in reqs)
        max_new = max(r.max_new_tokens for r in reqs)
        if not self.cfg.window and plen + max_new > self.max_len:
            # out-of-range positions would silently scatter-drop KV writes
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens ({max_new}) exceeds "
                f"engine max_len {self.max_len}")
        toks = np.full((self.B, plen), PAD_TOKEN, np.int32)
        for i, r in enumerate(reqs):
            toks[i, plen - len(r.prompt):] = r.prompt  # left-pad
        batch = {"tokens": jnp.asarray(toks), **self._stub_inputs(self.B)}
        cache, logits = self._prefill(self.params, batch)

        cur = jnp.asarray(plen - 1, jnp.int32)
        for t in range(max_new):
            self._key, k = jax.random.split(self._key)
            tokens = sample_tokens(logits, self.sampler, k)
            arr = np.asarray(tokens)
            for i, r in enumerate(reqs):
                if r.done or t >= r.max_new_tokens:
                    continue
                tok = int(arr[i])
                r.out.append(tok)
                if r.on_token is not None:
                    r.on_token(r, tok)
                if r.stop_token is not None and tok == r.stop_token:
                    r.done = True
            if all(r.done or len(r.out) >= r.max_new_tokens for r in reqs):
                break
            cur = cur + 1
            logits, cache = self._step(self.params, cache, tokens, cur)
        for r in requests:
            if not r.done and len(r.out) >= r.max_new_tokens:
                # budget exhausted (or zero budget): the request is finished
                # even without a stop-token hit — same completion semantics
                # as serve()'s on-device alive mask (live & !stop &
                # remaining > 0), so a run() result can never slip past the
                # scheduler's resubmission guard
                r.done = True
        return requests

    # ------------------------------------------------------------------
    # continuous batching (ScheduleBackend protocol; driven by the
    # ContinuousScheduler — see repro/serving/scheduler.py)
    # ------------------------------------------------------------------

    def _make_sched_step(self):
        """Fused per-step fn: sample → mask dead slots → advance per-slot
        positions → decode → on-device stop/budget masking.  The host sees
        only the (tokens, alive) pair."""
        cfg, sampler = self.cfg, self.sampler

        def step(p, state, key):
            live = state["live"]
            toks = sample_tokens(state["logits"], sampler, key)
            toks = jnp.where(live, toks, PAD_TOKEN)
            index = state["index"] + live  # only live slots advance
            # dead rows decode at the -1 sentinel: their KV/pos writes drop,
            # so a slot mid-chunked-prefill (or simply idle) never pollutes
            # the ring while decode steps interleave with admission chunks
            logits, cache = decode_step(p, cfg, state["cache"], toks,
                                        jnp.where(live, index, -1))
            remaining = state["remaining"] - live
            alive = live & (toks != state["stop"]) & (remaining > 0)
            state = dict(state, cache=cache, logits=logits, index=index,
                         remaining=remaining, stop=state["stop"], live=alive)
            return state, toks, alive

        return step

    def _make_spec_step(self):
        """Fused speculative round (continuous path, greedy only):

        1. the target's FREE next token ``c0 = argmax(state["logits"])`` —
           already exactly what the non-speculative step would emit;
        2. a K-step draft scan proposes ``c1..c_{K-1}`` greedily and writes
           ALL K candidates into the draft ring, so the draft cache stays
           position-synced for any acceptance count;
        3. one batched target ``verify_step`` scores all K candidates;
           candidate ``j >= 1`` is accepted iff it equals the target's own
           argmax after candidates ``0..j-1`` — i.e. iff it IS the token the
           sequential greedy engine would have emitted;
        4. stop/budget masking over the accepted window, then
           ``rollback_kv_window`` rewinds both rings past the accepted
           prefix.

        Dead rows (``live = False``) verify at position -1: no KV/pos write
        lands and ``n_acc = n_emit = 0``.  Returns ``(state, cands [B, K],
        n_acc [B], n_emit [B], alive [B])``.

        ``window`` (traced, [B] int32 in ``[2, K]``) caps the accepted
        prefix per slot: candidates at positions ``>= window[b]`` are
        treated as rejected, so ``n_acc[b] <= window[b]``.  The draft scan
        and verify still run all K positions — ONE compiled trace serves
        every window combination, and the rollback already rewinds whatever
        was not accepted — so ``window[b] = K`` reproduces the fixed-K round
        bit-for-bit.  The scheduler's dynamic-``spec_k`` policy sizes this
        from measured acceptance (the saved work shows up in the acceptance
        accounting, which charges only ``window - 1`` drafts per round).
        """
        from repro.kernels.dispatch import shard_scope
        from repro.models.decode import (rollback_kv_window,
                                         snapshot_kv_window, verify_step)

        cfg, dcfg, K = self.cfg, self.draft_cfg, self.spec_k
        dinfo = self._shard_infos.get("spec_draft")

        def step(p, dp, state, window):
            live = state["live"]
            index = state["index"]
            B = live.shape[0]
            start = jnp.where(live, index + 1, -1)
            c0 = jnp.where(live, greedy_tokens(state["logits"]), PAD_TOKEN)
            dcache = state["dcache"]
            with shard_scope(dinfo):
                dundo = snapshot_kv_window(dcfg, dcache, start, K)

                def draft_body(carry, j):
                    dc, tok = carry
                    dlogits, dc = decode_step(dp, dcfg, dc, tok,
                                              jnp.where(live, index + 1 + j,
                                                        -1))
                    nxt = jnp.where(live, greedy_tokens(dlogits), PAD_TOKEN)
                    return (dc, nxt), tok

                (dcache, _), cands = jax.lax.scan(
                    draft_body, (dcache, c0), jnp.arange(K, dtype=jnp.int32))
            cands = jnp.swapaxes(cands, 0, 1)  # [B, K]
            undo = snapshot_kv_window(cfg, state["cache"], start, K)
            vlogits, cache = verify_step(p, cfg, state["cache"], cands, start)
            pred = greedy_tokens(vlogits)  # [B, K]
            # accepted prefix: candidate j (>=1) must equal the target's
            # argmax after consuming candidates 0..j-1 AND sit inside the
            # slot's draft window; c0 is always accepted
            in_win = jnp.arange(1, K, dtype=jnp.int32)[None, :] < \
                window[:, None]
            match = ((cands[:, 1:] == pred[:, :-1]) & in_win).astype(
                jnp.int32)
            n_acc = jnp.where(
                live, 1 + jnp.sum(jnp.cumprod(match, axis=1), axis=1), 0)
            # stop/budget masking over the accepted window: emit up to (and
            # including) the first stop token, never past the budget
            j_iota = jnp.arange(K, dtype=jnp.int32)[None, :]
            is_stop = (j_iota < n_acc[:, None]) & \
                (cands == state["stop"][:, None])
            stop_at = jnp.min(jnp.where(is_stop, j_iota, K), axis=1)
            n_emit = jnp.minimum(jnp.minimum(n_acc, stop_at + 1),
                                 state["remaining"])
            remaining = state["remaining"] - n_emit
            stopped = stop_at < n_emit  # the stop token was actually emitted
            alive = live & ~stopped & (remaining > 0)
            cache = rollback_kv_window(cfg, cache, undo, n_acc)
            dcache = rollback_kv_window(dcfg, dcache, dundo, n_acc)
            # next round's free token comes from the target's logits at the
            # last accepted position (the "bonus" distribution verify paid
            # for); dead rows keep their stale logits untouched
            rows = jnp.arange(B)
            nlog = vlogits[rows, jnp.maximum(n_acc - 1, 0)]
            logits = jnp.where(live[:, None], nlog, state["logits"])
            state = dict(state, cache=cache, dcache=dcache, logits=logits,
                         index=index + n_acc, remaining=remaining,
                         stop=state["stop"], live=alive)
            return state, cands, n_acc, n_emit, alive

        return step

    def _state_template(self) -> dict:
        """The scheduler-state pytree (also eval_shape'd in mesh mode to
        derive the state shardings pinned on the jitted entry points)."""
        B, V = self.B, self.cfg.padded_vocab
        state = {
            "cache": init_cache(self.cfg, B, self.max_len),
            "logits": jnp.zeros((B, V), jnp.float32),
            "live": jnp.zeros((B,), bool),
            "index": jnp.zeros((B,), jnp.int32),
            "remaining": jnp.zeros((B,), jnp.int32),
            "stop": jnp.full((B,), -1, jnp.int32),
        }
        if self.spec_k:
            # the draft's KV ring rides in the scheduler state: its per-slot
            # position trajectory is the target's (admission and every spec
            # round write both in lockstep), so one `index` serves both
            state["dcache"] = init_cache(self.draft_cfg, B, self.max_len)
        return state

    def sched_start(self) -> dict:
        """Fresh scheduler state: empty cache, all slots dead.  In mesh mode
        the state is laid out per ``sharding.engine_state_specs`` up front,
        so the first jitted step never reshards."""
        state = self._state_template()
        if self.mesh is not None:
            state = jax.device_put(state, self._state_sh)
        return state

    def _validate_request(self, request: Request) -> int:
        plen = len(request.prompt)
        if plen < 1:
            raise ValueError("empty prompt")
        if not self.cfg.window and plen + request.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens ({request.max_new_tokens}) "
                f"exceeds engine max_len {self.max_len}")
        return plen

    @staticmethod
    def _admit_commit(state: dict, cache1: dict, logits1, slot, index0,
                      remaining, stop) -> dict:
        """Splice a fully-prefilled single-row cache into batch row ``slot``
        and arm the slot — the ONE place the per-slot arming invariant
        (cache/logits/live/index/remaining/stop) lives; both the chunked and
        the whole-prompt admission paths commit through it.  All scalars
        arrive as traced int32, so one trace serves every (slot,
        prompt-length, budget) combination."""
        def splice(big, one):
            idx = (0, slot) + (0,) * (big.ndim - 2)
            return jax.lax.dynamic_update_slice(big, one.astype(big.dtype), idx)

        return dict(
            state,
            cache=jax.tree.map(splice, state["cache"], cache1),
            logits=state["logits"].at[slot].set(logits1),
            live=state["live"].at[slot].set(True),
            index=state["index"].at[slot].set(index0),
            remaining=state["remaining"].at[slot].set(remaining),
            stop=state["stop"].at[slot].set(stop),
        )

    @staticmethod
    def _admit_commit_spec(state: dict, cache1: dict, dcache1: dict, logits1,
                           slot, index0, remaining, stop) -> dict:
        """Speculative variant of :meth:`_admit_commit`: the draft's freshly
        prefilled single-row cache is spliced into ``state["dcache"]`` at the
        same slot, so the slot's draft ring starts in lockstep with the
        target's (both hold the prompt's KV at positions ``0..plen-1``)."""
        state = DecodeEngine._admit_commit(state, cache1, logits1, slot,
                                           index0, remaining, stop)

        def splice(big, one):
            idx = (0, slot) + (0,) * (big.ndim - 2)
            return jax.lax.dynamic_update_slice(big, one.astype(big.dtype), idx)

        return dict(state,
                    dcache=jax.tree.map(splice, state["dcache"], dcache1))

    def _commit(self, state: dict, slot: int, cache1: dict, logits1,
                request: Request, dcache1: dict | None = None) -> dict:
        stop = -1 if request.stop_token is None else int(request.stop_token)
        scalars = (jnp.asarray(slot, jnp.int32),
                   jnp.asarray(len(request.prompt) - 1, jnp.int32),
                   jnp.asarray(request.max_new_tokens, jnp.int32),
                   jnp.asarray(stop, jnp.int32))
        if self.spec_k:
            return self._admit_commit_fn(state, cache1, dcache1, logits1,
                                         *scalars)
        return self._admit_commit_fn(state, cache1, logits1, *scalars)

    def sched_admit_start(self, state: dict, slot: int, request: Request):
        """Begin admitting ``request`` into ``slot``.  Returns
        ``(state, pending)``: ``pending is None`` means the admission
        completed atomically (whole-prompt fallback archs); otherwise feed it
        to :meth:`sched_admit_step` until it returns ``None`` — each call
        prefills one fixed-size prompt chunk, so a scheduler can interleave
        decode steps to bound co-batched time-to-first-token.

        The in-flight prefill runs against a private single-row cache and is
        spliced into the live batch only on the final chunk, so decode steps
        on the other rows proceed untouched throughout.

        With a prefix store, the store is consulted first: the longest
        hashed-prefix run of cached blocks is spliced into the private cache
        (jitted ``splice_block`` — NO prefill-chunk trace runs for a hit, so
        ``trace_counts`` stays honest) and chunked prefill resumes at the
        first miss.  The final chunk is always computed — the slot needs its
        last-position logits, which no KV slab carries."""
        plen = self._validate_request(request)
        if not self.chunked_admission:
            return self._admit_whole(state, slot, request), None
        C = self.prefill_chunk
        prompt = np.asarray(request.prompt, np.int32)
        chunks = []
        for start, valid in prefill_chunks_of(plen, C):
            toks = np.full((1, C), PAD_TOKEN, np.int32)
            toks[0, :valid] = prompt[start:start + valid]
            pos = np.full((1, C), -1, np.int32)
            pos[0, :valid] = np.arange(start, start + valid)
            chunks.append((jnp.asarray(toks), jnp.asarray(pos),
                           jnp.asarray(valid - 1, jnp.int32)))
        cache1 = init_cache(self.cfg, 1, self.max_len)
        if self.mesh is not None:
            # place the fresh cache in the layout the chunk fn returns: JAX
            # keys jit traces on input shardings, so an unplaced first chunk
            # would trace apart from every later one
            cache1 = jax.device_put(cache1, self._cache1_sh)
        hits, hashes = 0, []
        if self.prefix_store is not None:
            store = self.prefix_store
            hashes = store.block_hashes(prompt,
                                        n_blocks=self._publishable_blocks(plen))
            # reusable depth: full blocks strictly before the final chunk
            # (the final chunk always recomputes for its logits); the
            # publishable cap already bounded depth at the ring length
            n_reusable = min(len(chunks) - 1, len(hashes))
            hits = store.match(hashes[:n_reusable])
            for i in range(hits):
                slab = store.get(hashes[i])
                cache1 = self._splice_block_fn(
                    cache1, slab["k"], slab["v"],
                    jnp.asarray(i * C, jnp.int32))
            store.stats.reused_tokens += hits * C
        pending = {
            "request": request, "slot": slot, "plen": plen,
            "chunks": chunks, "i": hits, "hashes": hashes,
            "cache": cache1, "logits1": None,
            # draft prefill cursor: the draft has no prefix store, so it
            # computes EVERY chunk from 0 even when the target spliced hits —
            # prefix reuse composes with speculation without touching the
            # draft ring's contents
            "di": 0 if self.spec_k else len(chunks),
            "dcache": (init_cache(self.draft_cfg, 1, self.max_len)
                       if self.spec_k else None),
        }
        return state, pending

    def _publishable_blocks(self, plen: int) -> int:
        """How many leading full blocks of a ``plen``-token prompt the
        prefix store may hold: every full ``prefill_chunk`` block, capped on
        windowed configs at the blocks fully inside the first ``CL``
        positions — deeper blocks are overwritten in the ring before the
        prompt's tail attends them, so their slabs could neither be
        extracted after prefill-time wraparound nor spliced usefully."""
        n_full = plen // self.prefill_chunk
        if self.cfg.window:
            n_full = min(n_full, self._CL // self.prefill_chunk)
        return n_full

    def sched_admit_step(self, state: dict, pending: dict):
        """Advance an in-flight admission by one prompt chunk; on the final
        chunk splice the prefilled row into the live state and arm the slot.
        Returns ``(state, pending | None)``.

        When a prefix store is attached, each freshly-computed full block
        within reuse depth is extracted from the just-written ring slots and
        published, so the next request sharing the prefix splices instead of
        recomputing.

        With a draft model, each call also advances the draft's own prefill
        by one chunk (same tokens/positions, its private single-row cache),
        so admission completes with BOTH rings armed; total call count stays
        ``len(chunks)`` — the draft catches up during the calls the target
        skipped via prefix hits."""
        n = len(pending["chunks"])
        if pending["di"] < n:
            toks, pos, take = pending["chunks"][pending["di"]]
            pending["dcache"], _ = self._draft_prefill_chunk_fn(
                self.draft_params, pending["dcache"], toks, pos, take)
            pending["di"] += 1
        i = pending["i"]
        if i < n:
            toks, pos, take = pending["chunks"][i]
            pending["cache"], logits1 = self._prefill_chunk_fn(
                self.params, pending["cache"], toks, pos, take)
            if i < len(pending["hashes"]) and \
                    pending["hashes"][i] not in self.prefix_store:
                slab = self._extract_block_fn(
                    pending["cache"],
                    jnp.asarray(i * self.prefill_chunk, jnp.int32))
                self.prefix_store.put(pending["hashes"][i], slab)
            pending["i"] += 1
            if pending["i"] >= n:
                pending["logits1"] = logits1
        if pending["i"] < n or pending["di"] < n:
            return state, pending
        state = self._commit(state, pending["slot"], pending["cache"],
                             pending["logits1"][0], pending["request"],
                             dcache1=pending["dcache"])
        return state, None

    def prefix_match_len(self, request: Request) -> int:
        """Cached-prefix depth for ``request`` in TOKENS — how much prefill
        admission would skip right now.  A read-only probe (no LRU bump, no
        hit/miss accounting): the scheduler calls this per queued request to
        order admission by cache affinity, and a probe must not distort
        eviction order or the measured admission hit rate.  0 without a
        store."""
        if self.prefix_store is None:
            return 0
        plen = len(request.prompt)
        n_reusable = min((plen - 1) // self.prefill_chunk,
                         self._publishable_blocks(plen))
        if n_reusable <= 0:
            return 0
        hashes = self.prefix_store.block_hashes(request.prompt,
                                                n_blocks=n_reusable)
        return self.prefix_store.match(hashes, peek=True) * self.prefill_chunk

    def _admit_whole(self, state: dict, slot: int, request: Request) -> dict:
        """Whole-prompt fallback admission for architectures without
        chunked-prefill support: one single-row `prefill` (retraces per
        prompt length — the cost the chunked path avoids) committed through
        the same splice as the chunked path."""
        batch = {"tokens": jnp.asarray(np.asarray(request.prompt,
                                                  np.int32)[None]),
                 **self._stub_inputs(1)}
        cache1, logits = self._prefill(self.params, batch)
        return self._commit(state, slot, cache1, logits[0], request)

    def sched_admit(self, state: dict, slot: int, request: Request) -> dict:
        """Atomic admission: prefill ``request`` (chunked where supported)
        and splice it into batch row ``slot`` before returning."""
        state, pending = self.sched_admit_start(state, slot, request)
        while pending is not None:
            state, pending = self.sched_admit_step(state, pending)
        return state

    def sched_step(self, state: dict):
        self._key, k = jax.random.split(self._key)
        state, toks, alive = self._sched_step_fn(self.params, state, k)
        return state, np.asarray(toks), np.asarray(alive)

    #: sched_spec_step accepts per-slot draft windows (dynamic spec_k)
    spec_window_aware = True

    def sched_spec_step(self, state: dict, window=None):
        """One speculative round (ScheduleBackend accept/rollback protocol).
        Returns ``(state, cands [B, K], n_acc [B], n_emit [B], alive [B])``:
        slot ``b`` emits ``cands[b, :n_emit[b]]`` — every emitted token is
        the target's own greedy choice; ``n_acc - 1`` of them (live rows)
        were drafted.  ``window`` (length-B ints in ``[2, spec_k]``, None =
        full ``spec_k`` everywhere) caps each slot's accepted prefix — same
        compiled trace either way.  Greedy only; requires a ``draft`` at
        construction."""
        if not self.spec_k:
            raise RuntimeError("sched_spec_step requires draft= at engine "
                               "construction")
        if window is None:
            w = np.full((self.B,), self.spec_k, np.int32)
        else:
            w = np.asarray(window, np.int32)
            if w.shape != (self.B,):
                raise ValueError(f"window must have shape ({self.B},), got "
                                 f"{w.shape}")
        state, cands, n_acc, n_emit, alive = self._spec_step_fn(
            self.params, self.draft_params, state, w)
        return (state, np.asarray(cands), np.asarray(n_acc),
                np.asarray(n_emit), np.asarray(alive))

    def serve(self, requests: list[Request], *,
              on_token: Callable[[Request, int], None] | None = None,
              max_steps: int | None = None,
              admission_budget: int | None = None) -> list[Request]:
        """Run requests through the continuous-batching scheduler: FIFO
        admission, per-slot positions, finished slots refilled mid-flight.
        Any number of requests — slots turn over as requests finish.
        ``admission_budget`` caps prefill chunks per scheduler step (None =
        complete each admission immediately), bounding time-to-first-token
        for co-batched requests while a long prompt is admitted.
        Returns ``requests`` (same objects, ``out`` filled, in input order).
        """
        from repro.serving.scheduler import ContinuousScheduler

        sched = ContinuousScheduler(self, on_token=on_token,
                                    admission_budget=admission_budget)
        for r in requests:
            sched.submit(r)
        sched.run(max_steps=max_steps)
        return requests
