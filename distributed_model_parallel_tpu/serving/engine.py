"""ServingEngine: prefill/decode split with continuous batching over
the slot-paged KV cache.

One engine = one compiled prefill program + ONE compiled decode program
that advances EVERY cache slot a single token per call, whatever
position each slot sits at (the mixed-position batch is the point of
continuous batching — Orca, PAPERS.md). The host loop
(`ServingEngine.run`) does iteration-level scheduling: admit waiting
requests into free slots (prefill), one decode step for the active
set, evict finished sequences and recycle their slots.

The engine reaches a model through ONE seam, the `ServingFamily` its
configuration answers `serving_family()` with (`models/lm_family.py`):
the parameter init, a stem for each kind of step, the blocks given an
attention function and a state function, the head, and per layer what
the layer keeps between steps — pages of keys and values, pages of one
latent row a token (the latent pool), or arrays of constant size per
slot (the state pool beside the page pool, `serving/kv_cache.py`) —
and, where its layers count something a step at a time (an expert
layer's picks), the counters the engine accumulates on the device
beside the cache. It spells no family's fields. What a family
cannot run yet it names (`ServingFamily.missing`), and the engine
refuses each such option at construction under the option's name.

For `models/gpt.GPTConfig` the parameters are the dense
`models/gpt.gpt_lm` pytree — the SAME tree the
TP and SP-LM training engines train (`TrainState.params` serves
directly), placed per layout:

  replicated — params + cache replicated; plain jit.
  tp         — params sharded by `MEGATRON_RULES` on the 'model' axis
               (the TensorParallelEngine layout), cache head-sharded;
               GSPMD inserts the decode collectives — or, with
               `collective_matmul=True`, the opted-in projections ride
               chunked ppermute rings over the slot batch
               (`serving/decode.DecodeCollectiveMatmul`): exactly
               4·L·(S-1) permutes per decode step and no monolithic
               all-gather on the opted-in path (hlolint
               `serve-decode-ring`).
  sp         — cache position-sharded over 'seq'; decode merges
               per-shard partial attention via the online-softmax
               recurrence, and long prefill reuses the training ring
               (`ops/ring_attention.py`) over the same axis.

All three are logit-identical to full-sequence recompute at rtol 1e-5
(tests/test_serving.py) — the cache is an optimization, never an
approximation.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from functools import partial
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.observability.metrics import (
    get_metrics,
)
from distributed_model_parallel_tpu.observability.trace import get_tracer
from distributed_model_parallel_tpu.ops.attention import (
    dot_product_attention,
)
from distributed_model_parallel_tpu.ops.quant_matmul import (
    QuantMatmul,
    normalize_compute_dtype,
)
from distributed_model_parallel_tpu.ops.ring_attention import (
    ring_attention,
)
from distributed_model_parallel_tpu.runtime.compat import shard_map
from distributed_model_parallel_tpu.serving.decode import (
    CacheAttention,
    DecodeCollectiveMatmul,
    PagedCacheAttention,
    PagedChunkAttention,
    PagedLatentChunk,
    PagedLatentDecode,
    PagedSeqShardedCacheAttention,
    PagedVerifyAttention,
    PrefillRecorder,
    SeqShardedCacheAttention,
    SlotStateChunk,
    SlotStateDecode,
)
from distributed_model_parallel_tpu.serving.kv_cache import (
    KVCacheSpec,
    PagedCacheHost,
    PagedKVCacheSpec,
    StatePoolSpec,
    cache_pspecs,
    cache_shardings,
    copy_page,
    init_cache,
    init_paged_cache,
    init_state_pool,
    paged_pspecs,
    paged_shardings,
)
from distributed_model_parallel_tpu.serving.sampling import (
    SamplingConfig,
    SlotSampler,
)
from distributed_model_parallel_tpu.serving.scheduler import (
    PassRow,
    Request,
    Scheduler,
)


@contextlib.contextmanager
def _collector_pauses():
    """The seconds of every garbage collection that runs inside the
    block, as a list that fills while it runs: a collection holds the
    interpreter, so it stalls the loop whichever thread set it off. The
    hook costs nothing while the collector rests and is gone when the
    block ends, however it ends. Pauses are durations, read on
    `time.perf_counter` (the tracer's clock unless a test injected
    another, which the collector must not tick)."""
    pauses: List[float] = []
    began = []

    def hook(phase, info):
        if phase == "start":
            began.append(time.perf_counter())
        elif began:  # (not a collection under way as the hook went in)
            pauses.append(time.perf_counter() - began.pop())

    gc.callbacks.append(hook)
    try:
        yield pauses
    finally:
        gc.callbacks.remove(hook)


@jax.jit
def greedy_pick(logits):
    """Greedy sampling on the device: each row's argmax (the first of
    equal maxima, as NumPy's), so that the paged loop fetches one id a
    row and not the row of float32 logits (32 x 65,536 of them are
    8.4 MB a step, and the device idles while they cross)."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@dataclasses.dataclass
class ServingEngine:
    """Autoregressive serving of any configuration that answers
    `serving_family()` (module doc)."""

    cfg: Any
    mesh: Optional[Mesh] = None
    layout: str = "replicated"  # replicated | tp | sp
    num_slots: int = 4
    max_len: Optional[int] = None  # cache positions; <= max_position
    prefill_len: Optional[int] = None  # padded prompt length; <= max_len
    # Latency-hiding decode rings over 'model' (tp layout only):
    # `serving/decode.DecodeCollectiveMatmul`. Default off, same math.
    collective_matmul: bool = False
    # Decode-projection compute dtype: "f32" (default), "bf16"
    # (half-precision activations + cache, the MXU's native half path),
    # or "int8" (absmax-quantized projection GEMMs on the decode hot
    # floor — `ops/quant_matmul.py`; activations/cache stay f32, only
    # the opted-in projection dots quantize, prefill untouched). A
    # dtype object (jnp.bfloat16) is accepted for back-compat.
    compute_dtype: Any = None
    donate: bool = True  # donate the cache buffers step-over-step
    # --- block paging (PagedAttention; serving/kv_cache.py) ----------
    # page_size None = the contiguous slot layout above; set = the
    # page-pool layout: device K/V in (L, num_pages, page_size, H, Dh)
    # pages reached through a host block table, page-granular
    # alloc/free, logits pinned identical to the contiguous path.
    page_size: Optional[int] = None
    # Pool size in pages; None = num_slots * ceil(max_len/page_size)
    # (worst case — a smaller pool is the memory win, bounded by live
    # tokens).
    num_pages: Optional[int] = None
    # Chunked prefill: ingest prompts this many tokens per engine
    # iteration, sharing iterations with in-flight decode (admission
    # stops stalling the batch — Orca). None = monolithic prefill.
    # Requires page_size; replicated/tp layouts.
    prefill_chunk: Optional[int] = None
    # Prefix caching: share immutable prompt pages between slots via a
    # host-side token-prefix map (copy-on-write on the first divergent
    # write). Requires page_size + prefill_chunk; replicated/tp.
    prefix_cache: bool = False
    # Speculative decoding (serving/speculative.py — Leviathan ICML'23,
    # PAPERS.md): a draft engine proposes this many tokens per slot per
    # round and THIS engine scores all k+1 positions in one
    # chunked-prefill-shaped verify step; rejected suffixes roll back
    # by truncating the block table. 0 = off. Requires page_size (the
    # rollback is a block-table edit) and a non-sp layout; pass the
    # draft engine + params to `run`.
    speculative_k: int = 0

    def __post_init__(self):
        fam = self.family = self.cfg.serving_family()
        self.max_len = self.max_len or fam.max_position
        self.prefill_len = self.prefill_len or self.max_len
        if self.max_len > fam.max_position:
            raise ValueError(
                f"max_len {self.max_len} exceeds the position table "
                f"(cfg.max_position={fam.max_position})"
            )
        if not 1 <= self.prefill_len <= self.max_len:
            raise ValueError(
                f"prefill_len {self.prefill_len} must be in "
                f"[1, max_len={self.max_len}]"
            )
        # What the family cannot run yet, refused under the option's
        # name with the mechanism that is missing.
        asked = {
            "prefix_cache": self.prefix_cache,
            "speculative_k": bool(self.speculative_k),
            f"layout={self.layout}": True,
            "page_size=None": self.page_size is None,
            "prefill_chunk=None": self.prefill_chunk is None,
        }
        for option, why in fam.missing.items():
            if asked.get(option):
                raise ValueError(
                    f"{option} is not built for the {fam.name} family: "
                    f"{why}"
                )
        # Normalize the knob once: the string triple {"f32","bf16",
        # "int8"} is the engine/CLI surface; dtype objects map onto it.
        self.compute_mode = normalize_compute_dtype(self.compute_dtype)
        # Activation/cache dtype. int8 keeps BOTH f32: quantization
        # lives inside the projection GEMMs (per-token dynamic scales,
        # dequantized f32 out — ops/quant_matmul.py), never at rest.
        self._act_dtype = (
            jnp.bfloat16 if self.compute_mode == "bf16" else None
        )
        if self.compute_mode == "int8" and self.layout == "sp":
            raise ValueError(
                "compute_dtype='int8' quantizes the decode projections "
                "(replicated/tp layouts); the sp layout's shard_map "
                "decode has no quantized policy path"
            )
        cache_dtype = self._act_dtype or jnp.float32
        # Per layer, what it keeps between steps: pages (the layers
        # that hold them share one pool, so one shape) or state.
        paged_kinds = {
            (lc.kv_heads, lc.head_dim, lc.latent_dim)
            for lc in fam.layers if lc.kv_heads or lc.latent_dim
        }
        if len(paged_kinds) != 1:
            raise ValueError(
                f"the {fam.name} family's layers cache {sorted(paged_kinds)} "
                "(heads, width, latent row): one page pool holds one shape"
            )
        (kv_heads, head_dim, latent_dim), = paged_kinds
        paged_layers = sum(
            1 for lc in fam.layers if lc.kv_heads or lc.latent_dim
        )
        self.latent_dim = latent_dim
        stateful = tuple(
            (i, tuple((name, tuple(shape), dtype or cache_dtype)
                      for name, (shape, dtype) in lc.state.items()))
            for i, lc in enumerate(fam.layers) if lc.state
        )
        self.state_spec = (
            StatePoolSpec(self.num_slots, stateful) if stateful else None
        )
        # Which program the chunk step's state layers run their
        # recurrence in, as the family's own selector answers for
        # prefill_chunk positions (None: no state, or no chunk step).
        self.chunk_state_program = (
            fam.chunk_state_program(self.prefill_chunk)
            if stateful and fam.chunk_state_program and self.prefill_chunk
            else None
        )
        self.spec = KVCacheSpec(
            num_layers=paged_layers, num_slots=self.num_slots,
            max_len=self.max_len, num_heads=kv_heads,
            head_dim=head_dim, dtype=cache_dtype,
        )
        self.spec.validate(self.layout, self.mesh)
        self.paged_spec = None
        if self.page_size is None:
            for flag, name in ((self.prefill_chunk, "prefill_chunk"),
                               (self.num_pages, "num_pages")):
                if flag is not None:
                    raise ValueError(
                        f"{name} configures the paged KV layout; set "
                        "page_size as well (None = contiguous slots)"
                    )
            if self.prefix_cache:
                raise ValueError(
                    "prefix_cache shares POOL PAGES between slots; it "
                    "requires page_size (the contiguous layout has no "
                    "sharable unit)"
                )
        else:
            pages_per_slot = -(-self.max_len // self.page_size)
            self.paged_spec = PagedKVCacheSpec(
                num_layers=paged_layers, num_slots=self.num_slots,
                max_len=self.max_len, page_size=self.page_size,
                num_pages=(
                    self.num_pages
                    if self.num_pages is not None
                    else self.num_slots * pages_per_slot
                ),
                num_heads=kv_heads, head_dim=head_dim,
                dtype=cache_dtype,
                # a unit head axis would be padded to a tile of rows
                fold_heads=kv_heads == 1 and self.layout == "replicated",
                latent_dim=latent_dim,
            )
            self.paged_spec.validate(self.layout, self.mesh)
            if self.prefill_chunk is not None:
                if self.prefill_chunk < 1:
                    raise ValueError(
                        f"prefill_chunk must be >= 1, got "
                        f"{self.prefill_chunk}"
                    )
                if self.layout == "sp":
                    raise ValueError(
                        "prefill_chunk is not supported under the sp "
                        "layout: sp prefill rides the training ring "
                        "over 'seq' in one pass (use monolithic "
                        "prefill, or the replicated/tp layouts)"
                    )
            if self.prefix_cache:
                if self.layout == "sp":
                    raise ValueError(
                        "prefix_cache is not supported under the sp "
                        "layout (shared pages would need coherent "
                        "copy-on-write across 'seq' shards)"
                    )
                if self.prefill_chunk is None:
                    raise ValueError(
                        "prefix_cache needs chunked prefill "
                        "(prefill_chunk): a partial prefix hit resumes "
                        "ingestion mid-prompt, which only the chunked "
                        "path can do"
                    )
        if self.speculative_k:
            if not 1 <= self.speculative_k <= 8:
                raise ValueError(
                    f"speculative_k must be in [1, 8], got "
                    f"{self.speculative_k} (the verify step scores "
                    "k+1 positions in one compile; past ~8 the "
                    "acceptance tail pays for nothing)"
                )
            if self.layout == "sp":
                raise ValueError(
                    "speculative_k is not supported under the sp "
                    "layout: the verify step is a chunk-shaped batched "
                    "write the 'seq'-sharded shard_map decode has no "
                    "path for (same refusal shape as sp+int8) — use "
                    "the replicated/tp layouts"
                )
            if self.page_size is None:
                raise ValueError(
                    "speculative_k rolls rejected draft tokens back by "
                    "TRUNCATING THE BLOCK TABLE (freeing pages, never "
                    "copying KV); it requires the paged layout — set "
                    "page_size"
                )
            if self.speculative_k + 1 >= self.max_len:
                raise ValueError(
                    f"speculative_k {self.speculative_k} leaves no "
                    f"room: a verify round writes k+1 positions into a "
                    f"max_len={self.max_len} cache"
                )
        if self.collective_matmul and self.layout != "tp":
            raise ValueError(
                "collective_matmul=True rings decode projections over "
                "the 'model' axis; it requires layout='tp' "
                f"(got {self.layout!r})"
            )
        self._mm = None
        if self.layout == "tp":
            s = self.mesh.shape["model"]
            if self.num_slots % s:
                # The decode step keeps logits slot-sharded over
                # 'model' (no final gather inside the program), and the
                # opted-in rings chunk the slot batch — both need the
                # slot axis divisible. Fail here, not at trace time.
                raise ValueError(
                    f"tp layout shards the slot batch over 'model': "
                    f"num_slots {self.num_slots} not divisible by {s} "
                    "shards"
                )
            if self.collective_matmul:
                if s < 2:
                    raise ValueError(
                        "collective_matmul=True needs a 'model' axis "
                        ">= 2 to ring over (a 1-shard ring is a plain "
                        "dot)"
                    )
                for n, label in (
                    (self.num_slots, "num_slots"),
                    *((n, label) for label, n in fam.ring_widths.items()),
                ):
                    if n % s:
                        raise ValueError(
                            f"decode collective_matmul: {label} ({n}) "
                            f"must be divisible by the {s}-way 'model' "
                            "axis"
                        )
                self._mm = DecodeCollectiveMatmul(
                    mesh=self.mesh, axis="model",
                    compute_dtype=(
                        "int8" if self.compute_mode == "int8" else None
                    ),
                )
        # The decode-step projection policy: the opted-in rings when
        # built above; otherwise, under int8, the non-ring quantized
        # policy (replicated / tp-without-rings — GSPMD partitions the
        # int8 dots). Threaded ONLY into the decode steps — prefill
        # stays f32 (the decode hot floor is the target).
        self._decode_mm = self._mm
        if self.compute_mode == "int8" and self._mm is None:
            self._decode_mm = QuantMatmul()
        if self.layout == "sp":
            s = self.mesh.shape["seq"]
            if self.prefill_len % s:
                raise ValueError(
                    f"sp prefill shards the prompt over 'seq': "
                    f"prefill_len {self.prefill_len} not divisible by "
                    f"{s} shards"
                )
        # Dense-parameter twin: init + checkpoint interop with the
        # training engines (identical pytree).
        self._full = fam.model()
        self._blocks_state = {
            str(i): {} for i in range(len(fam.layers))
        }
        self._build_shardings()
        self._build_steps()

    # ------------------------------------------------------- shardings

    def _build_shardings(self):
        mesh = self.mesh
        if mesh is None:
            self._param_sh = self._cache_sh = self._repl = None
            self._paged_sh = None
            return
        self._repl = NamedSharding(mesh, P())
        if self.layout == "tp":
            from distributed_model_parallel_tpu.parallel.tensor_parallel import (  # noqa: E501
                MEGATRON_RULES,
                shard_specs,
            )

            key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
            p_aval, _ = jax.eval_shape(self._full.init, key_aval)
            self._param_sh = jax.tree_util.tree_map(
                lambda spec: NamedSharding(mesh, spec),
                shard_specs(p_aval, MEGATRON_RULES),
                is_leaf=lambda x: isinstance(x, P),
            )
        else:
            self._param_sh = self._repl
        self._cache_sh = cache_shardings(mesh, self.layout)
        self._paged_sh = None
        if self.paged_spec is not None:
            # K and V as the layout shards them; what rides in the
            # cache tree beside (or instead of) them replicated: the
            # latent pool, the state pool, the counters
            kv = paged_shardings(mesh, self.layout)
            self._paged_sh = {
                name: kv.get(name) or jax.tree_util.tree_map(
                    lambda _: self._repl, entry
                )
                for name, entry in jax.eval_shape(self._paged_cache).items()
            }

    def _paged_cache(self) -> dict:
        """The paged cache tree, zeroed: the pages, the state pool of a
        family that keeps one, the counters of one that counts."""
        out = init_paged_cache(self.paged_spec)
        if self.state_spec is not None:
            out["state"] = init_state_pool(self.state_spec)
        if self.family.counters is not None:
            # (whole numbers: a float32 sum stops being exact at 2**24
            # picks, minutes into a drain)
            counters = out["counters"] = {}
            for name, how in self.family.counter_reductions.items():
                if how != "last":
                    counters[name] = jnp.zeros((), jnp.int32)
                    continue
                # a step's own array, one entry a row of the step
                after = self.family.counter_rows[name]
                for kind, rows in (("decode", self.num_slots),
                                   ("chunk", self.prefill_chunk or 0)):
                    counters[f"{name}_{kind}"] = jnp.zeros(
                        (rows, *after), jnp.int32
                    )
        return out

    # ----------------------------------------------------------- steps

    def _build_steps(self):
        fam = self.family
        cdt = self._act_dtype
        num_slots = self.num_slots
        max_len = self.max_len
        p_len = self.prefill_len
        blocks_state = self._blocks_state
        mm = self._decode_mm
        ctx = L.Context(train=False, dtype=cdt)

        def apply_blocks(params, x, attention_fn, block_ctx,
                         state_fn=None):
            """-> (hidden, the blocks' post-forward state)."""
            blocks = L.sequential(*fam.blocks(attention_fn, state_fn))
            (h, _), after = blocks.apply(
                params["blocks"], blocks_state, x, block_ctx
            )
            return h, after

        def run_blocks(*args):
            return apply_blocks(*args)[0]

        # --- decode: one token for every slot, mixed positions -------
        def decode_step(params, cache, tokens, active):
            positions = cache["lengths"]
            rec = CacheAttention(
                cache["k"], cache["v"], positions, active
            )
            h = fam.decode_stem(params, tokens, positions, cdt)
            mask = jnp.ones((num_slots, 1), jnp.bool_)
            h = run_blocks(
                params, (h, mask), rec,
                dataclasses.replace(ctx, matmul=mm),
            )
            logits = fam.head(params, h)[:, 0, :]
            new_lengths = jnp.where(active, positions + 1, positions)
            new_cache = {
                "k": rec.k, "v": rec.v, "lengths": new_lengths,
            }
            return new_cache, logits

        def sp_decode_step(params, cache, tokens, active):
            positions = cache["lengths"]
            rec = SeqShardedCacheAttention(
                cache["k"], cache["v"], positions, active, axis="seq"
            )
            h = fam.decode_stem(params, tokens, positions, cdt)
            mask = jnp.ones((num_slots, 1), jnp.bool_)
            h = run_blocks(params, (h, mask), rec, ctx)
            logits = fam.head(params, h)[:, 0, :]
            new_lengths = jnp.where(active, positions + 1, positions)
            new_cache = {
                "k": rec.k, "v": rec.v, "lengths": new_lengths,
            }
            return new_cache, logits

        # --- prefill: one padded prompt into one slot ----------------
        def prefill_step(params, cache, ids, length, slot):
            mask = jnp.arange(p_len)[None, :] < length  # (1, P)
            h = fam.prefill_stem(params, ids, 0, cdt)
            rec = PrefillRecorder(
                partial(dot_product_attention, causal=True)
            )
            h = run_blocks(params, (h, mask), rec, ctx)
            logits = fam.head(params, h)  # (1, P, V) f32
            next_logits = lax.dynamic_index_in_dim(
                logits[0], length - 1, axis=0, keepdims=False
            )
            k_stack = jnp.stack([k[0] for k in rec.ks])  # (L,P,H,Dh)
            v_stack = jnp.stack([v[0] for v in rec.vs])
            pad = ((0, 0), (0, max_len - p_len), (0, 0), (0, 0))
            new_cache = {
                "k": lax.dynamic_update_slice(
                    cache["k"],
                    jnp.pad(k_stack, pad)[:, None].astype(
                        cache["k"].dtype
                    ),
                    (0, slot, 0, 0, 0),
                ),
                "v": lax.dynamic_update_slice(
                    cache["v"],
                    jnp.pad(v_stack, pad)[:, None].astype(
                        cache["v"].dtype
                    ),
                    (0, slot, 0, 0, 0),
                ),
                "lengths": cache["lengths"].at[slot].set(length),
            }
            return new_cache, next_logits

        def sp_prefill_step(params, cache, ids, length, slot):
            s = self.mesh.shape["seq"]
            tl = p_len // s
            chunk = max_len // s
            idx = lax.axis_index("seq")
            offset = idx * tl
            gmask = (offset + jnp.arange(tl))[None, :] < length
            h = fam.prefill_stem(params, ids, offset, cdt)
            rec = PrefillRecorder(
                partial(ring_attention, axis_name="seq", causal=True)
            )
            h = run_blocks(params, (h, gmask), rec, ctx)
            logits = fam.head(params, h)  # (1, tl, V)
            # The next-token logits live on the shard owning global
            # position length-1; psum broadcasts that one row.
            owner = (length - 1) // tl
            li = jnp.clip(length - 1 - offset, 0, tl - 1)
            row = jnp.where(
                idx == owner,
                lax.dynamic_index_in_dim(
                    logits[0], li, axis=0, keepdims=False
                ),
                jnp.zeros((fam.vocab_size,), jnp.float32),
            )
            next_logits = lax.psum(row, "seq")
            # Each cache shard owns positions [idx*chunk, (idx+1)*chunk);
            # gather the prompt K/V once, pad to max_len, keep my chunk.
            k_stack = jnp.stack([k[0] for k in rec.ks])  # (L,tl,H,Dh)
            v_stack = jnp.stack([v[0] for v in rec.vs])
            pad = ((0, 0), (0, max_len - p_len), (0, 0), (0, 0))

            def my_chunk(stack):
                full = jnp.pad(
                    lax.all_gather(stack, "seq", axis=1, tiled=True),
                    pad,
                )
                return lax.dynamic_slice_in_dim(
                    full, idx * chunk, chunk, axis=1
                )

            new_cache = {
                "k": lax.dynamic_update_slice(
                    cache["k"],
                    my_chunk(k_stack)[:, None].astype(cache["k"].dtype),
                    (0, slot, 0, 0, 0),
                ),
                "v": lax.dynamic_update_slice(
                    cache["v"],
                    my_chunk(v_stack)[:, None].astype(cache["v"].dtype),
                    (0, slot, 0, 0, 0),
                ),
                "lengths": cache["lengths"].at[slot].set(length),
            }
            return new_cache, next_logits

        # --- paged twins: pool + block table instead of dense slots --
        # `lengths` is NOT device state here — the host owns every
        # slot's position along with the block table, so positions ride
        # in as an argument and the cache pytree is exactly {k, v}.
        paged = self.paged_spec
        page = paged.page_size if paged else 0

        has_state = self.state_spec is not None
        latent = bool(self.latent_dim)

        def new_cache(rec, states, counters=None):
            """The cache tree after a step: the recorders' pages, the
            state pool where the family keeps one, its counters where
            it counts."""
            out = (
                {"latent": rec.pools} if latent
                else {"k": rec.k, "v": rec.v}
            )
            if states is not None:
                out["state"] = states.state
            if counters is not None:
                out["counters"] = counters
            return out

        def counted_blocks(params, cache, kind, *args):
            """`run_blocks`, and the cache's counters with this step's
            (`kind`: "decode" | "chunk") added as the family's
            reductions say; None for a family that counts nothing."""
            h, after = apply_blocks(params, *args)
            if fam.counters is None:
                return h, None
            how = {"sum": jnp.add, "max": jnp.maximum}
            step = fam.counters(after, kind)
            counters = dict(cache["counters"])
            for name, reduction in fam.counter_reductions.items():
                if reduction == "last":  # this kind of step's own
                    name, told = f"{name}_{kind}", step[name]
                    counters[name] = told.astype(counters[name].dtype)
                else:
                    counters[name] = how[reduction](
                        counters[name],
                        step[name].astype(counters[name].dtype),
                    )
            return h, counters

        def paged_decode_step(params, cache, bt, positions, tokens,
                              active):
            rec = (
                PagedLatentDecode(
                    cache["latent"], bt, positions, active, page
                ) if latent else PagedCacheAttention(
                    cache["k"], cache["v"], bt, positions, active, page
                )
            )
            states = (
                SlotStateDecode(cache["state"], active) if has_state
                else None
            )
            h = fam.decode_stem(params, tokens, positions, cdt)
            mask = (
                active[:, None] if fam.masks_inactive
                else jnp.ones((num_slots, 1), jnp.bool_)
            )
            h, counters = counted_blocks(
                params, cache, "decode", (h, mask), rec,
                dataclasses.replace(ctx, matmul=mm), states,
            )
            logits = fam.head(params, h)[:, 0, :]
            return new_cache(rec, states, counters), logits

        def sp_paged_decode_step(params, cache, bt, positions, tokens,
                                 active):
            rec = PagedSeqShardedCacheAttention(
                cache["k"], cache["v"], bt, positions, active, page,
                axis="seq",
            )
            h = fam.decode_stem(params, tokens, positions, cdt)
            mask = jnp.ones((num_slots, 1), jnp.bool_)
            h = run_blocks(params, (h, mask), rec, ctx)
            logits = fam.head(params, h)[:, 0, :]
            return {"k": rec.k, "v": rec.v}, logits

        def _scatter_slot_pages(buf, stack, bt_row):
            """(L, p_len, H, Dh) full-prompt K or V -> the slot's pool
            pages (drop unallocated entries)."""
            n_pages = paged.pages_per_slot
            pad = ((0, 0), (0, n_pages * page - p_len), (0, 0), (0, 0))
            pages = jnp.pad(stack, pad).reshape(
                stack.shape[0], n_pages, *buf.shape[2:]
            ).astype(buf.dtype)
            dst = jnp.where(bt_row >= 0, bt_row, paged.num_pages)
            return buf.at[:, dst].set(pages, mode="drop")

        def paged_prefill_step(params, cache, bt_row, ids, length):
            mask = jnp.arange(p_len)[None, :] < length
            h = fam.prefill_stem(params, ids, 0, cdt)
            rec = PrefillRecorder(
                partial(dot_product_attention, causal=True)
            )
            h = run_blocks(params, (h, mask), rec, ctx)
            logits = fam.head(params, h)
            next_logits = lax.dynamic_index_in_dim(
                logits[0], length - 1, axis=0, keepdims=False
            )
            k_stack = jnp.stack([k[0] for k in rec.ks])
            v_stack = jnp.stack([v[0] for v in rec.vs])
            return {
                "k": _scatter_slot_pages(cache["k"], k_stack, bt_row),
                "v": _scatter_slot_pages(cache["v"], v_stack, bt_row),
            }, next_logits

        def sp_paged_prefill_step(params, cache, bt_row, ids, length):
            s = self.mesh.shape["seq"]
            tl = p_len // s
            psub = page // s
            idx = lax.axis_index("seq")
            offset = idx * tl
            gmask = (offset + jnp.arange(tl))[None, :] < length
            h = fam.prefill_stem(params, ids, offset, cdt)
            rec = PrefillRecorder(
                partial(ring_attention, axis_name="seq", causal=True)
            )
            h = run_blocks(params, (h, gmask), rec, ctx)
            logits = fam.head(params, h)
            owner = (length - 1) // tl
            li = jnp.clip(length - 1 - offset, 0, tl - 1)
            row = jnp.where(
                idx == owner,
                lax.dynamic_index_in_dim(
                    logits[0], li, axis=0, keepdims=False
                ),
                jnp.zeros((fam.vocab_size,), jnp.float32),
            )
            next_logits = lax.psum(row, "seq")
            n_pages = paged.pages_per_slot
            pad = ((0, 0), (0, n_pages * page - p_len), (0, 0), (0, 0))

            def my_pages(buf, stack):
                full = jnp.pad(
                    lax.all_gather(stack, "seq", axis=1, tiled=True),
                    pad,
                )  # (L, max_len, H, Dh)
                pages = full.reshape(
                    stack.shape[0], n_pages, page, *stack.shape[2:]
                )
                mine = lax.dynamic_slice_in_dim(
                    pages, idx * psub, psub, axis=2
                ).astype(buf.dtype)
                dst = jnp.where(bt_row >= 0, bt_row, paged.num_pages)
                return buf.at[:, dst].set(mine, mode="drop")

            k_stack = jnp.stack([k[0] for k in rec.ks])
            v_stack = jnp.stack([v[0] for v in rec.vs])
            return {
                "k": my_pages(cache["k"], k_stack),
                "v": my_pages(cache["v"], v_stack),
            }, next_logits

        chunk = self.prefill_chunk or 0

        def chunk_prefill_step(params, cache, bt_row, ids, start,
                               n_valid, slot=None):
            # `slot`: the sequence's row of the state pool, handed in
            # by the host loop iff the family keeps one
            states = (
                SlotStateChunk(cache["state"], slot, start)
                if has_state else None
            )
            rec = (
                PagedLatentChunk(
                    cache["latent"], bt_row, start, n_valid, page
                ) if latent else PagedChunkAttention(
                    cache["k"], cache["v"], bt_row, start, page
                )
            )
            h = fam.chunk_stem(params, ids, start, cdt)
            mask = jnp.arange(chunk)[None, :] < n_valid
            h, counters = counted_blocks(
                params, cache, "chunk", (h, mask), rec, ctx, states
            )
            # the head on the one row the chunk needs
            next_logits = fam.head_row(params, h, n_valid - 1)
            return new_cache(rec, states, counters), next_logits

        # --- speculative verify: all slots' k+1-token spans, one step -
        # The chunk-shaped twin of paged_decode_step: same recorder
        # discipline (gather -> span write -> touched-page scatter),
        # same ctx.matmul policy threading — under tp+cm the flattened
        # slots*(k+1) rows ride the SAME 4·L·(S-1) serve_ring permute
        # chain as one decode step (hlolint `spec-verify-step`).
        spec_t = self.speculative_k + 1

        def paged_verify_step(params, cache, bt, positions,
                              tokens_chunk, active):
            rec = PagedVerifyAttention(
                cache["k"], cache["v"], bt, positions, active, page
            )
            h = fam.verify_stem(params, tokens_chunk, positions, cdt)
            mask = jnp.ones((num_slots, spec_t), jnp.bool_)
            h = run_blocks(
                params, (h, mask), rec,
                dataclasses.replace(ctx, matmul=mm),
            )
            logits = fam.head(params, h)  # (slots, k+1, V)
            return {"k": rec.k, "v": rec.v}, logits

        verify_fn = paged_verify_step if self.speculative_k else None

        donate = (1,) if self.donate else ()  # the cache argument
        self.verify_step = None
        if paged is not None:
            self._jit_paged_steps(
                paged_decode_step, sp_paged_decode_step,
                paged_prefill_step, sp_paged_prefill_step,
                chunk_prefill_step, verify_fn, donate,
            )
            return
        if self.layout == "sp":
            mesh = self.mesh
            cspec = cache_pspecs("sp")
            self.decode_step = jax.jit(
                shard_map(
                    sp_decode_step, mesh=mesh,
                    in_specs=(P(), cspec, P(), P()),
                    out_specs=(cspec, P()),
                    check_vma=False,
                ),
                donate_argnums=donate,
            )
            self.prefill = jax.jit(
                shard_map(
                    sp_prefill_step, mesh=mesh,
                    in_specs=(P(), cspec, P(None, "seq"), P(), P()),
                    out_specs=(cspec, P()),
                    check_vma=False,
                ),
                donate_argnums=donate,
            )
        elif self.mesh is not None:
            # replicated-with-mesh and tp: declarative placement; the
            # opted-in tp rings enter via ctx.matmul inside decode_step.
            logits_sh = (
                NamedSharding(self.mesh, P("model", None))
                if self.layout == "tp" else self._repl
            )
            self.decode_step = jax.jit(
                decode_step,
                in_shardings=(
                    self._param_sh, self._cache_sh, self._repl,
                    self._repl,
                ),
                out_shardings=(self._cache_sh, logits_sh),
                donate_argnums=donate,
            )
            self.prefill = jax.jit(
                prefill_step,
                in_shardings=(
                    self._param_sh, self._cache_sh, self._repl,
                    self._repl, self._repl,
                ),
                out_shardings=(self._cache_sh, self._repl),
                donate_argnums=donate,
            )
        else:
            self.decode_step = jax.jit(
                decode_step, donate_argnums=donate
            )
            self.prefill = jax.jit(
                prefill_step, donate_argnums=donate
            )

    def _jit_paged_steps(self, decode_fn, sp_decode_fn, prefill_fn,
                         sp_prefill_fn, chunk_fn, verify_fn, donate):
        """Compile the paged step set. Public surface:

        * `decode_step(params, cache, bt, positions, tokens, active)`
        * `prefill(params, cache, bt_row, ids, length)` — monolithic
        * `chunk_prefill(params, cache, bt_row, ids, start, n_valid)`
          (only when `prefill_chunk` is set; with a state pool a
          seventh argument follows, `slot`: the sequence's row of it)
        * `verify_step(params, cache, bt, positions, tokens_chunk,
          active)` — speculative k+1-position scoring (only when
          `speculative_k` is set); logits (slots, k+1, vocab)
        * `_copy_page(cache, src, dst)` — the COW kernel
          `PagedCacheHost` calls
        """
        self.chunk_prefill = None
        if self.layout == "sp":
            mesh = self.mesh
            cspec = paged_pspecs("sp")
            self.decode_step = jax.jit(
                shard_map(
                    sp_decode_fn, mesh=mesh,
                    in_specs=(P(), cspec, P(), P(), P(), P()),
                    out_specs=(cspec, P()),
                    check_vma=False,
                ),
                donate_argnums=donate,
            )
            self.prefill = jax.jit(
                shard_map(
                    sp_prefill_fn, mesh=mesh,
                    in_specs=(P(), cspec, P(), P(None, "seq"), P()),
                    out_specs=(cspec, P()),
                    check_vma=False,
                ),
                donate_argnums=donate,
            )
            self._copy_page = jax.jit(
                copy_page,
                in_shardings=(self._paged_sh, self._repl, self._repl),
                out_shardings=self._paged_sh,
                donate_argnums=(0,),
            )
            return
        if self.mesh is not None:
            logits_sh = (
                NamedSharding(self.mesh, P("model", None))
                if self.layout == "tp" else self._repl
            )
            r = self._repl
            self.decode_step = jax.jit(
                decode_fn,
                in_shardings=(
                    self._param_sh, self._paged_sh, r, r, r, r,
                ),
                out_shardings=(self._paged_sh, logits_sh),
                donate_argnums=donate,
            )
            self.prefill = jax.jit(
                prefill_fn,
                in_shardings=(self._param_sh, self._paged_sh, r, r, r),
                out_shardings=(self._paged_sh, r),
                donate_argnums=donate,
            )
            self._copy_page = jax.jit(
                copy_page,
                in_shardings=(self._paged_sh, r, r),
                out_shardings=self._paged_sh,
                donate_argnums=(0,),
            )
            if self.prefill_chunk:
                self.chunk_prefill = jax.jit(
                    chunk_fn,
                    in_shardings=(
                        self._param_sh, self._paged_sh, r, r, r, r,
                        *((r,) if self.state_spec is not None else ()),
                    ),
                    out_shardings=(self._paged_sh, r),
                    donate_argnums=donate,
                )
            if verify_fn is not None:
                # Verify logits stay slot-sharded over 'model' under
                # tp, like decode's — the host reads every row anyway.
                vlogits_sh = (
                    NamedSharding(self.mesh, P("model", None, None))
                    if self.layout == "tp" else self._repl
                )
                self.verify_step = jax.jit(
                    verify_fn,
                    in_shardings=(
                        self._param_sh, self._paged_sh, r, r, r, r,
                    ),
                    out_shardings=(self._paged_sh, vlogits_sh),
                    donate_argnums=donate,
                )
            return
        self.decode_step = jax.jit(decode_fn, donate_argnums=donate)
        self.prefill = jax.jit(prefill_fn, donate_argnums=donate)
        self._copy_page = jax.jit(copy_page, donate_argnums=(0,))
        if self.prefill_chunk:
            self.chunk_prefill = jax.jit(
                chunk_fn, donate_argnums=donate
            )
        if verify_fn is not None:
            self.verify_step = jax.jit(verify_fn, donate_argnums=donate)

    # ------------------------------------------------------------ state

    def init_params(self, rng: jax.Array):
        """Fresh parameters of the family's whole model (for GPT the
        `gpt_lm(cfg)` pytree — a trained TrainState.params from the TP /
        SP-LM engines drops in via `place_params`), in the dtype the
        family's weights rest in and in this layout's placement: one
        jitted call, so no float32 copy of a whole tree that rests in
        less ever exists."""
        at_rest = self.family.param_dtype

        def init(rng):
            params, _ = self._full.init(rng)
            if at_rest is None:
                return params
            return jax.tree_util.tree_map(
                lambda x: x.astype(at_rest), params
            )

        return jax.jit(init, out_shardings=self._param_sh)(rng)

    def place_params(self, params):
        """Place an existing dense-layout param pytree (a checkpoint or
        a training engine's canonical params) into this layout."""
        if self._param_sh is None:
            return params
        return jax.device_put(params, self._param_sh)

    def init_cache(self) -> dict:
        if self.paged_spec is not None:
            cache = self._paged_cache()
            if self._paged_sh is None:
                return cache
            return jax.device_put(cache, self._paged_sh)
        cache = init_cache(self.spec)
        if self._cache_sh is None:
            return cache
        return jax.device_put(cache, self._cache_sh)

    def new_host(self) -> PagedCacheHost:
        """Fresh host half of the paged cache (block tables + page
        pool + prefix map); one per `run` / test harness."""
        if self.paged_spec is None:
            raise ValueError(
                "new_host() is the paged layout's bookkeeping; set "
                "page_size"
            )
        return PagedCacheHost(
            self.paged_spec, prefix_cache=self.prefix_cache,
            copy_fn=self._copy_page,
        )

    # ---------------------------------------------------------- serving

    def pad_prompt(self, prompt: np.ndarray):
        """(ids (1, prefill_len) int32, length int32) for one prompt."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if not 1 <= prompt.size <= self.prefill_len:
            raise ValueError(
                f"prompt length {prompt.size} must be in "
                f"[1, prefill_len={self.prefill_len}]"
            )
        ids = np.zeros((1, self.prefill_len), np.int32)
        ids[0, : prompt.size] = prompt
        return jnp.asarray(ids), jnp.int32(prompt.size)

    def _pick(self, sampler: Optional[SlotSampler], logits_row,
              slot: int) -> int:
        """Next token id: greedy argmax (bit-stable, the default) or
        the per-slot sampling lane."""
        row = np.asarray(logits_row)
        if sampler is None:
            return int(row.argmax())
        return sampler.pick(row, slot)

    @property
    def _slot_stripe_bytes(self) -> int:
        """Contiguous-equivalent bytes one live slot would pin (the
        scheduler's SlotAllocator accounting seam): a `max_len` stripe
        of every paged layer's K and V, and the slot's row of the state
        pool."""
        s = self.spec
        # (a latent pool keeps one row a token, stored in whole lane
        # tiles, not K and V per head)
        per_token = (
            self.paged_spec.latent_width if self.latent_dim
            else 2 * s.num_heads * s.head_dim
        )
        return (
            s.num_layers * s.max_len * per_token
            * jnp.dtype(s.dtype).itemsize
        ) + (self.state_spec.slot_bytes if self.state_spec else 0)

    def run(self, params, requests: Sequence[Request],
            sampling: Optional[SamplingConfig] = None, *,
            draft: Optional["ServingEngine"] = None,
            draft_params=None) -> Scheduler:
        """Offline continuous batching: drive the request set to
        completion (greedy decoding by default; pass a SamplingConfig
        for temperature/top-k/top-p with per-slot PRNG lanes),
        returning the Scheduler with its per-request `finished` records
        and `latency_report()`. With `speculative_k` set, pass the
        draft engine and its params — the loop moves to
        `serving/speculative.run_speculative` (draft-propose, one-pass
        verify, lossless accept)."""
        sampler = (
            SlotSampler(sampling, self.num_slots)
            if sampling is not None and not sampling.greedy else None
        )
        if self.speculative_k:
            if draft is None or draft_params is None:
                raise ValueError(
                    "speculative_k > 0 needs a proposer: pass "
                    "run(..., draft=<draft ServingEngine>, "
                    "draft_params=<its params>)"
                )
            from distributed_model_parallel_tpu.serving.speculative import (  # noqa: E501
                run_speculative,
            )

            return run_speculative(
                self, params, requests, sampler, draft, draft_params
            )
        if draft is not None or draft_params is not None:
            raise ValueError(
                "draft/draft_params drive speculative decoding; set "
                "speculative_k > 0 on the target engine as well"
            )
        if self.paged_spec is not None:
            with _collector_pauses() as gc_pauses:
                return self._run_paged(
                    params, requests, sampler, gc_pauses
                )
        return self._run_contiguous(params, requests, sampler)

    def _run_contiguous(self, params, requests: Sequence[Request],
                        sampler: Optional[SlotSampler]) -> Scheduler:
        tracer = get_tracer()
        mx = get_metrics()  # per-call histograms; one branch when off
        sched = Scheduler(
            self.num_slots, self.max_len,
            bytes_per_slot=self._slot_stripe_bytes,
        )
        for r in requests:
            if r.prompt.size > self.prefill_len:
                raise ValueError(
                    f"request {r.rid!r}: prompt length {r.prompt.size} "
                    f"exceeds prefill_len {self.prefill_len}"
                )
            sched.submit(r)
        cache = self.init_cache()
        tokens = np.zeros((self.num_slots,), np.int32)
        active = np.zeros((self.num_slots,), bool)
        while sched.has_work():
            # Admission: prefill waiting requests into free slots.
            while sched.can_admit():
                seq = sched.admit()
                ids, length = self.pad_prompt(seq.request.prompt)
                t0 = tracer.now()
                with tracer.span("prefill", rid=repr(seq.request.rid),
                                 slot=seq.slot):
                    cache, next_logits = self.prefill(
                        params, cache, ids, length, jnp.int32(seq.slot)
                    )
                    tok = self._pick(sampler, next_logits, seq.slot)
                sched.emit(seq, tok, tracer.now())
                # A monolithic prefill is one engine iteration in which
                # exactly ONE slot did useful work — the admission
                # stall the chunked path removes, made visible in the
                # iteration-occupancy series.
                sched.record_iteration(1)
                if mx.enabled:
                    mx.observe(
                        "serve_prefill_s", seq.t_first_token - t0
                    )
                    # The prefill produced this request's FIRST token;
                    # decode steps count theirs in record_decode_step,
                    # so the counter totals to the report's
                    # generated_tokens exactly.
                    mx.inc("serve_tokens_total", 1)
                tokens[seq.slot] = tok
                active[seq.slot] = True
                if seq.done(self.max_len):
                    sched.finish(seq.slot)
                    active[seq.slot] = False
            if not active.any():
                continue
            # One decode step for the whole mixed-position batch.
            n_active = int(active.sum())
            t0 = tracer.now()
            with tracer.span("decode_step", active=n_active):
                cache, logits = self.decode_step(
                    params, cache, jnp.asarray(tokens),
                    jnp.asarray(active),
                )
                logits_np = np.asarray(logits)
            t1 = tracer.now()
            dt = t1 - t0
            sched.record_decode_step(n_active)
            sched.record_iteration(n_active)
            if mx.enabled:
                mx.observe("serve_decode_step_s", dt)
            for slot, seq in list(sched.active.items()):
                tok = self._pick(sampler, logits_np[slot], slot)
                sched.emit(seq, tok, t1, dt)
                tokens[slot] = tok
                if seq.done(self.max_len):
                    sched.finish(slot)
                    active[slot] = False
        return sched

    # ----------------------------------------------------- paged loop

    def _run_paged(self, params, requests: Sequence[Request],
                   sampler: Optional[SlotSampler],
                   gc_pauses: List[float]) -> Scheduler:
        """Continuous batching over the PAGE POOL: page-granular
        admission, optional chunked prefill (one `prefill_chunk`-token
        ingest per ingesting slot per engine iteration, SHARING the
        iteration with the in-flight decode step — a long prompt never
        stalls the batch), optional prefix caching (a cached prompt
        skips its prefill; its last partial page copies on the first
        divergent write). Every pass leaves one `PassRow` and every
        token its stamp with the scheduler, whether or not the tracer
        is on; `paged_stats["timeline"]` is their reduction, with the
        collector's pauses `run` gathered in `gc_pauses`."""
        tracer = get_tracer()
        mx = get_metrics()
        host = self.new_host()
        sched = Scheduler(
            self.num_slots, self.max_len,
            bytes_per_slot=self._slot_stripe_bytes,
        )
        chunked = bool(self.prefill_chunk)
        # Chunked ingestion walks the prompt in place, so the padded
        # prefill_len compile no longer caps prompt length — only the
        # cache (room for >= 1 generated token) does.
        cap = (self.max_len - 1) if chunked else self.prefill_len
        for r in requests:
            if r.prompt.size > cap:
                raise ValueError(
                    f"request {r.rid!r}: prompt length {r.prompt.size} "
                    f"exceeds "
                    + (f"max_len - 1 = {cap}" if chunked
                       else f"prefill_len {cap}")
                )
            sched.submit(r)
        cache = self.init_cache()
        positions = np.zeros((self.num_slots,), np.int32)
        tokens = np.zeros((self.num_slots,), np.int32)
        active = np.zeros((self.num_slots,), bool)
        # slot -> [prompt, next ingest position, accumulated seconds]
        ingest: dict = {}
        # Exact slot-step counts over the decode steps (the steps
        # `step_occupancy` samples), so that with it they sum to
        # num_slots x decode steps; they leave in `paged_stats`.
        tally = dict.fromkeys((
            "slot_steps_ingesting", "slot_steps_page_blocked",
            "slot_steps_drain_out", "slot_steps_free_other",
            "admit_page_blocked_iters",
            # chunked prefill: prompt positions ingested, positions the
            # chunk program ran over (every chunk is padded to
            # prefill_chunk), and chunks that began a prompt, each of
            # which starts its slot's row of the state pool from zeros
            "prefill_positions_valid", "prefill_positions_computed",
            "state_resets",
            # chunks whose program ran the state layers' recurrence as
            # the kernel (0 for a page-only family and off a TPU)
            "state_kernel_chunks",
        ), 0)
        kernel_chunk = int(self.chunk_state_program == "kernel")
        state_pool_bytes = (
            self.state_spec.pool_bytes if self.state_spec else 0
        )
        latent_pool_bytes = (
            self.paged_spec.num_pages * self.paged_spec.page_bytes
            if self.latent_dim else 0
        )
        if mx.enabled:
            mx.gauge("serve_latent_pool_bytes", latent_pool_bytes)
            mx.gauge("serve_state_pool_bytes", state_pool_bytes)
            mx.gauge("serve_state_scan_kernel", float(kernel_chunk))
        # the chunk step of a family with a state pool also takes the
        # slot, its row of the pool; a page-only family's has no such
        # argument
        state_row = (
            (lambda slot: (np.int32(slot),)) if self.state_spec
            else (lambda slot: ())
        )
        # What the host fetches of a step's logits, and how it reads a
        # row's token there: greedy picks on the device and fetches the
        # ids, a sampler draws from the fetched rows.
        if sampler is None:
            to_fetch, pick = greedy_pick, (lambda row, slot: int(row))
        else:
            to_fetch, pick = (lambda logits: logits), sampler.pick

        def evict(slot):
            sched.finish(slot)
            active[slot] = False
            host.release(slot)

        # Passes tile the loop: one begins at the reading the last
        # ended with, so their walls add up to the loop's.
        t_iter = tracer.now()
        while sched.has_work() or ingest:
            useful = 0
            # The pass as a decoding user feels it: `engine_iter` with
            # the queue's state at its start; every stretch inside has
            # a child span (observability/metrics.py TRACE_EVENT_NAMES)
            # that carries the pass's number, and its seconds go into
            # the pass's row.
            n_pass = len(sched.passes)
            chunks, prefill_s, decode_s = 0, 0.0, 0.0
            n_waiting, n_ingesting = len(sched.waiting), len(ingest)
            n_decoding = len(sched.active) - n_ingesting
            # ---- admission: free slots AND page headroom -----------
            # The headroom check budgets the WHOLE sequence (prompt +
            # its max_new_tokens growth, capped by the cache) against
            # the pool minus every already-admitted slot's outstanding
            # commitment, and `reserve` records the same number — an
            # admitted request can always allocate to completion; a
            # request the pool cannot yet hold WAITS instead of
            # crashing mid-ingest.
            page_blocked = False
            with tracer.span("admit"):
                while sched.can_admit():
                    nxt = sched.waiting[0][1]
                    budget = min(
                        int(nxt.prompt.size) + int(nxt.max_new_tokens),
                        self.max_len,
                    )
                    if not host.can_hold(budget):
                        page_blocked = True
                        tally["admit_page_blocked_iters"] += 1
                        break
                    seq = sched.admit()
                    host.reserve(seq.slot, budget)
                    prompt = seq.request.prompt
                    covered = host.attach_prefix(seq.slot, prompt)
                    if mx.enabled and host.prefix is not None:
                        mx.inc(
                            "serve_prefix_hits_total",
                            1 if covered else 0,
                        )
                    if not chunked:
                        # Monolithic paged prefill: the padded
                        # one-compile prompt ingest, landing in pages.
                        host.ensure_pages(seq.slot, int(prompt.size))
                        ids, length = self.pad_prompt(prompt)
                        t0 = tracer.now()
                        with tracer.span(
                            "prefill", rid=repr(seq.request.rid),
                            slot=seq.slot, iter=n_pass,
                        ):
                            cache, nl = self.prefill(
                                params, cache,
                                host.device_row(seq.slot), ids, length,
                            )
                            tok = self._pick(sampler, nl, seq.slot)
                        t1 = tracer.now()
                        chunks += 1
                        prefill_s += t1 - t0
                        sched.emit(seq, tok, t1)
                        sched.record_iteration(1)
                        if mx.enabled:
                            mx.observe("serve_prefill_s", t1 - t0)
                            mx.inc("serve_tokens_total", 1)
                        tokens[seq.slot] = tok
                        positions[seq.slot] = prompt.size
                        active[seq.slot] = True
                        if seq.done(self.max_len):
                            evict(seq.slot)
                    elif (covered >= prompt.size - 1
                          and self.state_spec is None):
                        # (A state pool has no cached state to resume
                        # from, so there every prompt ingests: its first
                        # chunk is what resets the slot's state.)
                        # Full prefix hit: every needed position is
                        # cached — SKIP prefill entirely and decode the
                        # last prompt token at its own position. Its
                        # write page copies first if shared
                        # (copy-on-write), via the pre-decode
                        # ensure_writable pass every active slot goes
                        # through below.
                        positions[seq.slot] = prompt.size - 1
                        tokens[seq.slot] = int(prompt[-1])
                        active[seq.slot] = True
                    else:
                        ingest[seq.slot] = [prompt, covered, 0.0]
            # ---- ingestion: one chunk per ingesting slot -----------
            for slot in sorted(ingest):
                prompt, start, acc = ingest[slot]
                seq = sched.active[slot]
                n = min(self.prefill_chunk, int(prompt.size) - start)
                tally["prefill_positions_valid"] += n
                tally["prefill_positions_computed"] += self.prefill_chunk
                tally["state_kernel_chunks"] += kernel_chunk
                if state_pool_bytes and start == 0:
                    tally["state_resets"] += 1
                host.ensure_pages(slot, start + n)
                ids = np.zeros((1, self.prefill_chunk), np.int32)
                ids[0, :n] = prompt[start:start + n]
                t0 = tracer.now()
                with tracer.span(
                    "prefill_chunk", rid=repr(seq.request.rid),
                    slot=slot, start=start, iter=n_pass,
                ):
                    with tracer.span("dispatch"):
                        # (host values as they are: the step's call
                        # uploads them with the launch, where an array
                        # made of each first is a transfer of its own)
                        cache, nl = self.chunk_prefill(
                            params, cache, host.device_row(slot),
                            ids, np.int32(start), np.int32(n),
                            *state_row(slot),
                        )
                    done_ingest = start + n >= prompt.size
                    if done_ingest:
                        # The wait also holds the unfetched chunks
                        # dispatched before this one. Tracing off, the
                        # fetch alone blocks, as it always did.
                        if tracer.enabled:
                            with tracer.span("device_wait"):
                                jax.block_until_ready(nl)
                        with tracer.span("logits_fetch"):
                            row = np.asarray(to_fetch(nl))
                        with tracer.span("sample"):
                            tok = pick(row, slot)
                t1 = tracer.now()
                dt = t1 - t0
                chunks += 1
                prefill_s += dt
                useful += 1
                if done_ingest:
                    sched.emit(seq, tok, t1)
                    if mx.enabled:
                        mx.observe("serve_prefill_s", acc + dt)
                        mx.inc("serve_tokens_total", 1)
                    tokens[slot] = tok
                    positions[slot] = prompt.size
                    active[slot] = True
                    host.register_prefix(slot, prompt)
                    del ingest[slot]
                    if seq.done(self.max_len):
                        evict(slot)
                else:
                    ingest[slot][1] = start + n
                    ingest[slot][2] = acc + dt
            # ---- one decode step for the active set ----------------
            n_active = int(active.sum())
            if n_active:
                with tracer.span("cow"):
                    for slot in np.nonzero(active)[0]:
                        cache = host.ensure_writable(
                            cache, int(slot), int(positions[slot])
                        )
                t0 = tracer.now()
                with tracer.span(
                    "decode_step", active=n_active, iter=n_pass
                ):
                    with tracer.span("dispatch"):
                        cache, logits = self.decode_step(
                            params, cache, host.device_table(),
                            positions, tokens, active,
                        )
                    if tracer.enabled:
                        with tracer.span("device_wait"):
                            jax.block_until_ready(logits)
                    with tracer.span("logits_fetch"):
                        rows = np.asarray(to_fetch(logits))
                t1 = tracer.now()
                decode_s = dt = t1 - t0
                sched.record_decode_step(n_active)
                # Where this step's other slot-steps went: every slot
                # is decoding (step_occupancy), ingesting, or free, and
                # a free slot is charged to the one reason it is free.
                free = self.num_slots - len(sched.active)
                tally["slot_steps_ingesting"] += len(ingest)
                if page_blocked:
                    tally["slot_steps_page_blocked"] += free
                elif not sched.waiting:
                    tally["slot_steps_drain_out"] += free
                else:
                    # freed after this pass's admission had run
                    tally["slot_steps_free_other"] += free
                if mx.enabled:
                    mx.observe("serve_decode_step_s", dt)
                useful += n_active
                with tracer.span("sample"):
                    for slot, seq in list(sched.active.items()):
                        if slot in ingest or not active[slot]:
                            continue
                        # One stamp for the step's tokens: when its
                        # fetch returned. (A full prefix hit's FIRST
                        # token arrives here too: its whole "prefill"
                        # was the cache lookup.)
                        tok = pick(rows[slot], slot)
                        sched.emit(seq, tok, t1, dt)
                        tokens[slot] = tok
                        positions[slot] += 1
                        if seq.done(self.max_len):
                            evict(slot)
            if mx.enabled:
                mx.gauge(
                    "serve_kv_pages_in_use", host.pool.pages_in_use
                )
            if useful:
                sched.record_iteration(useful)
            elif not ingest and not sched.active and sched.waiting:
                raise RuntimeError(
                    "page pool cannot hold the next waiting prompt "
                    f"({int(sched.waiting[0][1].prompt.size)} tokens, "
                    f"{host.pool.free_pages} free pages of "
                    f"{self.paged_spec.page_size}) — size the pool "
                    "larger (num_pages / --kv-pages)"
                )
            t_end = tracer.now()
            sched.passes.append(PassRow(
                t_iter, t_end, chunks, n_active, n_waiting,
                prefill_s, decode_s,
            ))
            if tracer.enabled:
                tracer.complete(
                    "engine_iter", t_iter, t_end, index=n_pass,
                    waiting=n_waiting, ingesting=n_ingesting,
                    active=n_decoding,
                )
            t_iter = t_end
        sched.paged_stats = {
            "page_size": self.paged_spec.page_size,
            "num_pages": self.paged_spec.num_pages,
            "pages_in_use_peak": host.pages_in_use_peak,
            "kv_cache_bytes_peak": (
                host.pages_in_use_peak * self.paged_spec.page_bytes
                + state_pool_bytes
            ),
            "state_pool_bytes": state_pool_bytes,
            "latent_pool_bytes": latent_pool_bytes,
            "contiguous_bytes": (
                self.num_slots * self._slot_stripe_bytes
            ),
            "cow_copies": host.cow_copies,
            **tally,
            # what the family's layers counted, step by step on the
            # device (an expert layer's picks): fetched once, here
            **{
                name: int(value) for name, value in
                jax.device_get(cache.get("counters", {})).items()
                if not value.shape  # (a step's own arrays stay there)
            },
            # The one entry that holds times (Scheduler.timeline): its
            # counts repeat from run to run like the rest, its seconds
            # are this run's alone.
            "timeline": sched.timeline(gc_pauses),
        }
        if host.prefix is not None:
            total_prompt = sum(
                int(r.prompt.size) for r in requests
            )
            sched.prefix_stats = {
                "hits": host.prefix.hits,
                "misses": host.prefix.misses,
                "tokens_reused": host.prefix.tokens_reused,
                "prefix_hit_pct": round(
                    100.0 * host.prefix.tokens_reused
                    / max(total_prompt, 1), 2
                ),
            }
        return sched


__all__ = ["ServingEngine"]
