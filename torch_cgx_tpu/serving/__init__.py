"""Serving data plane: paged quantized KV-cache wire for disaggregated
prefill/decode with continuous batching (PR 15 — docs/SERVING.md).

The training fabric's whole value proposition — fewer bytes per
collective through bucketwise max-min quantization over a hardened
two-level transport — applied to the latency-critical KV hop of
inference:

* :mod:`.kv_cache` — fixed-size page pool, per-sequence page tables,
  refcounted free lists; pages quantized under the ``kv_page`` wire
  edge kind.
* :mod:`.transport` — disaggregated prefill→decode shipping of
  quantized pages over the shm/store bridge with publish-after-write
  counter streams (decode never blocks on prefill).
* :mod:`.adapter` — what a model adapter is (``Adapter``, ``ServeConfig``)
  and what it may use of the cache: page specs, lane and ring masks, the
  paged read.
* :mod:`.programs` — the seven compiled programs over an adapter's cache
  streams (paged gather with the dequantize fused into the KV read) and
  the state they hand one another.
* :mod:`.scheduler` — continuous-batching decode: admit/evict per step,
  the program LRU, bounded prefill-failover instead of wedging.
* :mod:`.gpt2` — the GPT-2 adapter (streams ``k``, ``v``).
* :mod:`.latent` — the latent-attention (MLA) adapter with dropless
  experts (streams ``c``, ``kr``).
* :mod:`.hybrid` — the hybrid adapters: pages on their few attention
  layers (``k``, ``v``; ``c``, ``kr`` where the attention is latent), a
  per-lane recurrent state on the others (``conv``, ``ssm`` on Mamba-2
  layers; ``conv`` and ``gdn`` or ``kda``, a matrix a head, on delta-rule
  layers).
* :mod:`.window` — the window/global adapter: ``k``, ``v`` pages on every
  layer, a sliding-window layer's kept as a ring a lane beside the global
  page table: SmallThinker's block (dropless experts routed from the
  block's input) and Trinity's (``afmoe``: sandwich norms, gated QK-normed
  attention, a held share of experts beside a shared one).
* :mod:`.loop` — the looped adapter: ``k``, ``v`` pages on every layer, the
  layers run ``cache_passes`` times a token and every pass keeps pages and
  tails of its own (Ouro's block).
* :mod:`.block` — the block-diffusion adapter: ``k``, ``v`` pages on every
  layer, a decode step runs a block of ``block_tokens`` positions a lane and
  either denoises it (nothing stored) or stores it and emits its tokens
  (SDAR's block: QK-normed grouped-query attention, dropless experts).
* :mod:`.slo` — the WireController's serving objective: re-solve KV
  bit-width per layer against TTFT / tokens-per-second SLOs from the
  live metric stream.
"""

from .kv_cache import PagedKvCache, resolve_kv_config  # noqa: F401
from .adapter import ServeConfig  # noqa: F401
from .gpt2 import GPT2Server  # noqa: F401
from .scheduler import (  # noqa: F401
    ContinuousBatchScheduler,
    Request,
    invalidate_decode_cache,
)
from .latent import LatentMoEServer  # noqa: F401
from .hybrid import (  # noqa: F401
    HybridGDNServer,
    HybridLatentMoEServer,
    HybridSSMServer,
)
from .window import AfmoeServer, WindowMoEServer  # noqa: F401
from .loop import LoopServer  # noqa: F401
from .block import BlockDiffusionServer  # noqa: F401
from .slo import ServeSloController  # noqa: F401
from .transport import KvPageReceiver, KvPageSender  # noqa: F401
