"""What a serving engine asks of a model family, so that
``serving/engine.py`` names no model (ROADMAP D1). One protocol, answered by
the configuration object and by the module class it builds;
``models/llama.py``'s ``LlamaConfig`` / ``Llama``, ``models/nemotron_h.py``'s
``NemotronHConfig`` / ``NemotronH``, ``models/solar_open2.py``'s
``SolarOpen2Config`` / ``SolarOpen2``, ``models/deepseek_v3.py``'s
``DeepseekV3Config`` / ``DeepseekV3``, ``models/cohere2_moe.py``'s
``Cohere2MoeConfig`` / ``Cohere2Moe``, ``models/jamba.py``'s ``JambaConfig`` /
``Jamba``, ``models/zaya.py``'s ``ZayaConfig`` / ``Zaya``,
``models/minicpm_sala.py``'s ``MiniCPMSalaConfig`` / ``MiniCPMSala``,
``models/brumby.py``'s ``BrumbyConfig`` / ``Brumby``, ``models/ouro.py``'s
``OuroConfig`` / ``Ouro``, ``models/dots3_note.py``'s ``Dots3NoteConfig`` /
``Dots3Note``, ``models/motif.py``'s ``MotifConfig`` / ``Motif`` and
``models/longcat_flash.py``'s ``LongcatFlashConfig`` / ``LongcatFlash`` all
do: thirteen families.

**What a layer carries between its blocks is the model's own.** The engine
hands token ids in and takes logits out; ``models/motif.py`` carries **four
residual streams a token** (``[B, T, 4 x 4096]`` float32, mixed into and out of
every sublayer by matrices computed from the token: ``ops/mhc.py``) and no
cache leaf holds one: a carry of several streams asks nothing of the protocol.

**The configuration object** gives ``max_seq_len``, ``vocab_size``,
``dtype``, ``n_heads`` and, a model whose pages hold keys and values a head,
``n_kv_heads`` and ``head_dim`` (the paged pool's and the attention kernel's
shapes there: :class:`HeadPool` at the foot of this file reads them, the
engine reads neither), and:

- ``serving_config()``: itself with every training-only feature cleared;
- ``paged_model(page_size=, kv_pages=, kernel=, kv_quant=)``: the module
  the engine runs, for decode rounds and batch-1 prefill alike, reading its
  pool through the page table (``ops/paged_attention.py``, ``ops/mla.py``);
  a ``kv_quant`` its pool cannot take is refused here, by name;
- ``kv_layers``: entries a token keeps in the paged pool, one a layer that
  keeps a leaf there; **it may exceed the model's layers** (``models/ouro.py``
  runs its 48 layers four times a token with a cache a (pass, layer): 192;
  ``models/longcat_flash.py`` holds **two attentions of different weights a
  layer, each with a latent leaf of its own**: twice its layers). **0 (and no
  ``kv_window``) is a model with no pool** (``models/brumby.py``: every cache
  leaf is ``state``): the engine builds no block for it, not even the scratch
  one (``RadixCache(0, ..)``), keeps and uploads no page table (a decode round
  is handed the ``[slots]`` vector of live rows in the table's place, as
  ``valid_len``), admits a request by a free slot and ``max_seq_len`` alone,
  grows nothing in decode, reports ``kv_blocks_*`` 0, and refuses
  ``kv_blocks`` / ``kv_pool_bytes`` by name; its module is still handed
  ``page_table=`` (``[1, 0]`` in a prefill program, nothing in a decode round)
  and does not read it. The engine checks the answer against the module's leaf
  kinds and refuses a model whose two answers disagree;
- ``kv_token_bytes(kv_quant)``: the bytes one cached token costs one such
  layer. The engine divides a byte budget for the pool by
  ``page_size x kv_layers x kv_token_bytes`` and asks nothing about what a
  page holds: keys and values a head answer ``2 x KV x D`` elements (what
  ``kv_cache.kv_block_bytes`` counts a position), a latent cache with no
  head axis answers its one vector (``models/deepseek_v3.py``: 576 values
  in 640 lanes, 1280 bytes);
- ``read_path(kernel, t=, kv_quant=)``: the
  ``lzy_kernel_dispatch_total{path}`` label of the read of the pool by a
  program over ``t`` positions a row (``pallas`` / ``lax`` for keys and
  values a head; a latent pool's two reads have labels of their own);
  ``read_path(kernel, t=1)`` is the engine's ``kernel_path``;
- ``widest_prefill``: the widest prefill program (query positions of one
  batch-1 chunk) the model's kernels take; the engine's chunk width is the
  widest bucket under it and under the round's ``prefill_budget``. A model
  of dense matmuls answers the widest bucket; one whose kernel's
  arithmetic grows faster than its rows answers where that stops paying;
- ``kernel_paths(t)``: ``lzy_kernel_dispatch_total{path}`` labels of a
  program over ``t`` positions a row, beside the read's own;
- ``check_kernels(slots=, kv_blocks=, page_size=, pages_per_seq=,
  kv_quant=)``: lower the model's kernels for a TPU at the decode step's
  shapes, its read of a pool of these shapes among them (its own lowering:
  the engine names no kernel), so that what the lowering refuses is refused
  at construction; with no pool named, the kernels beside the read only.

A configuration whose module has ``window`` leaves (below) gives three answers
more, and the engine asks them of no other: ``kv_window``, the positions such a
leaf keeps readable behind a row's newest (a query at ``p`` reads ``p -
kv_window < j <= p`` there and nothing older, ever); ``window_layers``, the
layers that keep one (``kv_layers`` counts the layers of ``paged`` leaves
only); and ``paged_model`` / ``check_kernels`` take ``window_pages=`` /
``window_blocks=``, the second pool's size. **The window kind has its own
price**: ``window_token_bytes(kv_quant)``, optional, the bytes one cached token
costs one ``window`` layer; absent, it is ``kv_token_bytes``
(``models/cohere2_moe.py``: keys and values of the same heads in both kinds).
``models/dots3_note.py`` answers both: its window layers cache a latent vector
of their own rank, wider than the full layers' (2,304 bytes against 1,536), and
``serving/kv_cache.py`` ``divide_pool`` charges each kind its own;
``models/motif.py`` keeps **two latent leaves of one price in two kinds**
(``latent``, kind ``paged``, in its full layers and ``wlatent``, kind
``window``, in its window layers, 1,280 bytes a token a layer each) and answers
``kv_token_bytes`` alone. Such a module's ``__call__`` takes ``window_table=``
beside ``page_table=``: the same shape, addressing the ``window`` leaves.

**The module class** declares ``CACHE_KINDS`` (cache leaf name -> kind; a
leaf it does not name is ``paged``) and ``STATS``: the counters its
``stats`` collection feeds, in the order of the vector its layers sow
(summed over layers by the engine and carried out of a decode round with
its tokens; every ``stats`` leaf is one such vector, a layer filling its own
places); empty for a model that sows none. A model with state leaves or with
counts is told which positions are real (``valid_len``). A class that
declares ``TOLD_PROMPT_LEN`` is also handed ``prompt_len`` ``[1]`` in every
prefill program, the length of the prompt the request was admitted with
(``models/minicpm_sala.py`` fixes a request's mode by it and keeps the
answer in a ``state`` leaf, which is all a decode round is told).

**Cache leaves have kinds**:

- ``index``: tokens resident a row. The engine keeps one position vector and
  places it at every index leaf.
- ``paged``: a pool of pages shared by all slots, ``[pages, page, ...]``,
  addressed through a page table; what follows the page axis is the
  model's (``[KV, D]`` keys or values, a latent vector, ``[KV, page, D]``
  with the page axis second and ``[page / 16, KV, D]`` compressed keys
  beside them under the same table: ``models/minicpm_sala.py``; **two
  leaves of different widths under one table**, a latent vector of 640
  lanes and an indexer's key of 128, a layer: ``models/dots3_note.py``,
  whose ``kv_token_bytes`` is the two together; **a block
  that holds several passes**, ``[pages, T, page, KV, D]``, which the
  model reads inside pass ``t`` as ``[pages x T, page, KV, D]`` through
  ``page_table x T + t``: ``models/ouro.py``, where ``kv_layers`` counts the
  ``T`` entries a layer and one page table a row serves every pass). A batch-1 prefill
  writes the same pool; a prefix can be shared, exported, demoted, and a
  speculated position rewound by moving an index: every mechanism moves
  pages by block id and reads no shape past the page axis, so a latent
  leaf is served by all of them (``docs/serving.md`` has the table).
  Block 0 is the scratch block no row owns, so a row whose table starts
  with the scratch block has no real position: the decode read
  (``ops/paged_attention.py``) gives it 0 and reads nothing for it.
- ``window``: a pool of pages like ``paged`` (keys and values a head, or
  **a latent vector**: ``models/dots3_note.py``'s ``wlatent``), with a
  second lifetime: a token's entry stops being read ``kv_window`` positions behind the row's
  newest, so the engine returns a page that lies wholly behind the window
  of everything it has dispatched (a round in flight, the slot's prefill
  job) to an allocator of its own (``serving/kv_cache.py``
  ``WindowPages``), where another row can take it, and the row's window
  table reads scratch there. A row holds at most
  ``ceil((kv_window + chunk) / page) + 1`` such pages however long its
  context. Block counts, page tables and free lists are apart from the
  ``paged`` leaves'; a request is admitted when both can hold it. What was
  read through a window cannot be shared by a prefix's tokens (the pages
  behind it are gone), so the radix cache is off and the mechanisms that
  move pages by tokens refuse such a model by name (``docs/serving.md``).
- ``state``: ``[slots, ...]``, one row a slot (a recurrence's state, a
  convolution's window; ``models/zaya.py``'s is the last two positions'
  latent projections and nothing else: a window, not a recurrence). A prefill job carries its own batch-1 row between
  chunks and the engine splices it into the slot's row when the prompt is
  done; a padded chunk and an idle slot must not advance it (the engine
  passes ``valid_len``); nothing can share, export or rewind it, so a model
  with state leaves turns off the prefix cache and is refused by the
  mechanisms that would need to (``docs/serving.md``).
"""

from __future__ import annotations

from typing import Any, Optional

INDEX, PAGED, STATE, WINDOW = "index", "paged", "state", "window"
#: the kinds whose leaves are pools of pages shared by all slots
POOLS = (PAGED, WINDOW)


def leaf_kind(model: Any, path) -> str:
    """The kind of ``model``'s cache leaf at ``path``, by its own name."""
    return type(model).CACHE_KINDS.get(getattr(path[-1], "key", None), PAGED)


class HeadPool:
    """The answers of a configuration whose pages hold keys and values a
    head (``models/llama.py``, ``models/nemotron_h.py``,
    ``models/solar_open2.py``, ``models/zaya.py``: pools ``[pages, page, KV,
    D]``, twice, read by ``ops/paged_attention.py``), from its ``n_heads``, ``n_kv_heads``,
    ``head_dim`` and ``dtype``. ``models/jamba.py`` takes the bytes a token
    from here and answers ``read_path`` and its lowering itself: its pools
    are ``[pages, page, KV x D]``, read by ``paged_group_attention``."""

    def kv_token_bytes(self, kv_quant: Optional[str] = None) -> int:
        """Bytes one cached token costs one pool layer: keys and values a
        head, what ``kv_cache.kv_block_bytes`` counts a position a layer."""
        from lzy_tpu.serving.kv_cache import kv_block_bytes

        return kv_block_bytes(page_size=1, n_kv_heads=self.n_kv_heads,
                              head_dim=self.head_dim, dtype=self.dtype,
                              kv_quant=kv_quant)

    def read_path(self, kernel: str, *, t: int,
                  kv_quant: Optional[str] = None) -> str:
        """``lzy_kernel_dispatch_total{path}`` label of the attention read
        of a program over ``t`` positions a row."""
        from lzy_tpu.ops.paged_attention import kernel_path

        return kernel_path(kernel, t=t, quantized=kv_quant is not None)

    def lower_read(self, *, slots: int, kv_blocks: Optional[int],
                   page_size: Optional[int], pages_per_seq: Optional[int],
                   kv_quant: Optional[str]) -> None:
        """Lower the reads of a key/value pool of these shapes for a TPU,
        where the programs take the kernels: the decode read over every
        slot, and the chunk read of one row at the widest prefill width
        (no other width: a width is compiled by the first request that
        reaches it); nothing where the caller names no pool."""
        from lzy_tpu.ops.paged_attention import (
            CHUNK_PATH, lower_pallas_for_tpu)

        if kv_blocks is None:
            return
        for batch, t, path in ((slots, 1, "pallas"),
                               (1, self.widest_prefill, CHUNK_PATH)):
            if self.read_path("pallas", t=t, kv_quant=kv_quant) == path:
                lower_pallas_for_tpu(
                    batch=batch, t=t, n_heads=self.n_heads,
                    n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
                    n_blocks=kv_blocks, page_size=page_size,
                    pages_per_seq=pages_per_seq, dtype=self.dtype)
