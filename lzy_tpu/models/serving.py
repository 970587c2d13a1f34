"""What a serving engine asks of a model family, so that
``serving/engine.py`` names no model (ROADMAP D1). One protocol, answered by
the configuration object and by the module class it builds;
``models/llama.py``'s ``LlamaConfig`` / ``Llama``,
``models/nemotron_h.py``'s ``NemotronHConfig`` / ``NemotronH`` and
``models/solar_open2.py``'s ``SolarOpen2Config`` / ``SolarOpen2`` all do.

**The configuration object** gives ``max_seq_len``, ``vocab_size``,
``dtype``, ``n_heads``, ``n_kv_heads`` and ``head_dim`` (the paged pool's and
the attention kernel's shapes), and:

- ``serving_config()``: itself with every training-only feature cleared;
- ``paged_model(page_size=, kv_pages=, kernel=, kv_quant=)``: the module
  the engine runs, for decode rounds and batch-1 prefill alike, reading its
  pool through the page table (``ops/paged_attention.py``);
- ``kv_layers``: layers that keep keys and values in the paged pool, what a
  byte budget for the pool is divided by;
- ``widest_prefill``: the widest prefill program (query positions of one
  batch-1 chunk) the model's kernels take; the engine's chunk width is the
  widest bucket under it and under the round's ``prefill_budget``. A model
  of dense matmuls answers the widest bucket; one whose kernel's
  arithmetic grows faster than its rows answers where that stops paying;
- ``kernel_paths(t)``: ``lzy_kernel_dispatch_total{path}`` labels of a
  program over ``t`` positions a row, beside the attention read's own;
- ``check_kernels(slots=)``: lower the model's own kernels for a TPU at the
  decode step's shapes, so that what the lowering refuses is refused at
  construction.

**The module class** declares ``CACHE_KINDS`` (cache leaf name -> kind; a
leaf it does not name is ``paged``) and ``STATS``: the counters its
``stats`` collection feeds, in the order of the vector its layers sow
(summed over layers by the engine and carried out of a decode round with
its tokens); empty for a model that sows none.

**Cache leaves have kinds**:

- ``index``: tokens resident a row. The engine keeps one position vector and
  places it at every index leaf.
- ``paged``: a pool of pages shared by all slots, addressed through a page
  table. A batch-1 prefill writes the same pool; a prefix can be shared,
  exported, demoted, and a speculated position rewound by moving an index.
- ``state``: ``[slots, ...]``, one row a slot (a recurrence's state, a
  convolution's window). A prefill job carries its own batch-1 row between
  chunks and the engine splices it into the slot's row when the prompt is
  done; a padded chunk and an idle slot must not advance it (the engine
  passes ``valid_len``); nothing can share, export or rewind it, so a model
  with state leaves turns off the prefix cache and is refused by the
  mechanisms that would need to (``docs/serving.md``).
"""

from __future__ import annotations

from typing import Any

INDEX, PAGED, STATE = "index", "paged", "state"


def leaf_kind(model: Any, path) -> str:
    """The kind of ``model``'s cache leaf at ``path``, by its own name."""
    return type(model).CACHE_KINDS.get(getattr(path[-1], "key", None), PAGED)
