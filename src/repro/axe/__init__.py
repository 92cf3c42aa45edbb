"""AxeSpec end-to-end: one layout spec from the device mesh to the
Pallas block (docs/axespec.md), plus the multi-granularity kernel DSL
written against it (docs/kernel-dsl.md).

* ``repro.axe.spec``      — :class:`AxeSpec` + :class:`PhysicalSpace`
* ``repro.axe.lower``     — the two lowering adapters
  (AxeSpec → NamedSharding, AxeSpec → Pallas grid + BlockSpec)
* ``repro.axe.propagate`` — layout propagation over op graphs
* ``repro.axe.rules``     — the sharding rule engine (params / batches /
  caches), formerly the PartitionSpec tables in ``train.sharding``
* ``repro.axe.program``   — ``axe.program`` / ``@axe.kernel``: kernels
  as graphs of scope-tagged stages (MESH / GRID / BLOCK), schedules
  keyed ``program_name/stage_name`` through ``repro.tune``
* ``repro.axe.stages``    — the :class:`Stage` unit + scope validation
* ``repro.axe.compile``   — ``axe.compile``: GraphSpec + LayoutPlan →
  a jitted :class:`Executable` whose ops bind to the kernel programs
  and whose redistributions are real collectives (docs/compile.md)
* ``repro.axe.passes``    — graph-level fusion passes run before
  solve/compile: epilogue fusion, reshape-pair collapse, DCE
  (docs/passes.md)
"""
from repro.axe.spec import AxeSpec, PhysicalSpace, SpecError
from repro.axe.program import (
    PROGRAMS,
    Epilogue,
    KernelFallbackWarning,
    Program,
    ProgramError,
    StageContext,
    get_program,
    kernel,
    program,
)
from repro.axe.stages import Stage, StageError
from repro.axe.lower import (
    BlockLowering,
    block_lowering,
    from_pspec,
    from_sharding,
    layout_of_pspec,
    pspec_of_layout,
    spec_of_block,
    to_blockspec,
    to_named_sharding,
    to_pspec,
)
from repro.axe.propagate import (
    LayoutPlan,
    OpNode,
    PlanEntry,
    PropagationError,
    Redistribution,
    propagate,
    propagate_matmul,
    redistribute,
)
from repro.axe.graphs import (
    GraphSpec,
    TensorMeta,
    cache_window,
    decode_graph,
    decoder_layer_graph,
    model_graph,
)
from repro.axe.hetero import (
    ClassTable,
    DeviceClass,
    HeteroError,
    class_table,
    default_class_table,
    parse_classes,
    use_class_table,
)
from repro.axe.solve import (
    Decision,
    SolveError,
    SolveResult,
    enumerate_specs,
    solve,
)
from repro.axe.cotune import (
    CotuneIteration,
    CotuneResult,
    cotune,
)
from repro.axe.passes import (
    DeadCodeElimination,
    EpilogueFusion,
    FusionReport,
    Pass,
    PassError,
    PassPipeline,
    PassReport,
    Pattern,
    ReshapePairCollapse,
    default_pipeline,
    fuse_graph,
)
from repro.axe.compile import (
    BindReport,
    CompileError,
    Executable,
    LoweredOp,
    bind_report,
    compile,
    compiled_loss_fn,
    decode_cache,
    decode_executable,
    decode_inputs,
    model_executable,
    model_inputs,
    op_backend,
    plan_covers,
    register_op_backend,
)

__all__ = [
    "AxeSpec",
    "BlockLowering",
    "ClassTable",
    "BindReport",
    "CompileError",
    "CotuneIteration",
    "CotuneResult",
    "DeadCodeElimination",
    "Decision",
    "DeviceClass",
    "Epilogue",
    "EpilogueFusion",
    "Executable",
    "FusionReport",
    "GraphSpec",
    "HeteroError",
    "LoweredOp",
    "LayoutPlan",
    "OpNode",
    "PROGRAMS",
    "Pass",
    "PassError",
    "PassPipeline",
    "PassReport",
    "Pattern",
    "PhysicalSpace",
    "PlanEntry",
    "Program",
    "KernelFallbackWarning",
    "ProgramError",
    "PropagationError",
    "Redistribution",
    "ReshapePairCollapse",
    "SolveError",
    "SolveResult",
    "SpecError",
    "Stage",
    "StageContext",
    "StageError",
    "TensorMeta",
    "block_lowering",
    "bind_report",
    "cache_window",
    "class_table",
    "compile",
    "compiled_loss_fn",
    "cotune",
    "default_class_table",
    "decode_cache",
    "decode_executable",
    "decode_graph",
    "decode_inputs",
    "decoder_layer_graph",
    "default_pipeline",
    "enumerate_specs",
    "fuse_graph",
    "get_program",
    "kernel",
    "model_executable",
    "model_graph",
    "model_inputs",
    "op_backend",
    "parse_classes",
    "plan_covers",
    "program",
    "register_op_backend",
    "solve",
    "use_class_table",
    "from_pspec",
    "from_sharding",
    "layout_of_pspec",
    "propagate",
    "propagate_matmul",
    "pspec_of_layout",
    "redistribute",
    "spec_of_block",
    "to_blockspec",
    "to_named_sharding",
    "to_pspec",
]
