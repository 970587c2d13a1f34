from lzy_tpu.parallel.mesh import (AXES, MeshSpec, dp_mesh, fsdp_mesh,
                                   hybrid_mesh, mesh_for)
from lzy_tpu.parallel.sharding import (
    DEFAULT_RULES,
    infer_param_logical_axes,
    named_sharding,
    shard_tree,
    spec_for,
    tree_shardings,
)
from lzy_tpu.parallel.train import (
    PEAK_TFLOPS,
    chip_peak_tflops,
    TrainState,
    make_eval_step,
    make_train_step,
    mfu,
    transformer_flops_per_token,
)
from lzy_tpu.parallel.ring import ring_attention
from lzy_tpu.parallel.distributed import initialize_gang

__all__ = [
    "AXES",
    "MeshSpec",
    "dp_mesh",
    "fsdp_mesh",
    "mesh_for",
    "hybrid_mesh",
    "DEFAULT_RULES",
    "infer_param_logical_axes",
    "named_sharding",
    "shard_tree",
    "spec_for",
    "tree_shardings",
    "PEAK_TFLOPS",
    "chip_peak_tflops",
    "TrainState",
    "make_eval_step",
    "make_train_step",
    "mfu",
    "transformer_flops_per_token",
    "ring_attention",
    "initialize_gang",
]

from lzy_tpu.parallel.checkpoint import CheckpointManager  # noqa: E402

__all__.append("CheckpointManager")

from lzy_tpu.parallel.ulysses import ulysses_attention  # noqa: E402

__all__.append("ulysses_attention")

from lzy_tpu.parallel.orbax_interop import export_orbax, import_orbax  # noqa: E402

__all__ += ["export_orbax", "import_orbax"]
