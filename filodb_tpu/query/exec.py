"""ExecPlan — the distributed execution tree (facade).

Mirrors the reference's exec framework (ref: query/.../exec/ExecPlan.scala:41,
RangeVectorTransformer.scala:36, AggrOverRangeVectors.scala, BinaryJoinExec.scala,
DistConcatExec.scala, StitchRvsExec.scala) with a TPU-first data plane:

  - Leaves gather a shard's matching series into ONE dense [S, T] batch
    (RawBlock) instead of per-partition iterators.
  - PeriodicSamplesMapper runs the fused window kernel (ops/rangefns.py) on
    device producing a step-grid ResultBlock [S, W].
  - AggregateMapReduce emits mesh-reducible partial components; the
    map/reduce/present 3-phase contract is identical to the reference
    (doc/query-engine.md:311-330) so partials can ride psum collectives.

Dispatchers decouple tree topology from placement: InProcessPlanDispatcher
runs a subtree inline; the cluster layer adds remote dispatch.

Round 4: the implementation lives in execbase / transformers / leafexec /
nonleaf / metaexec (each under 800 LoC); this module re-exports every name
so existing import paths keep working.
"""
from filodb_tpu.query.execbase import (  # noqa: F401
    AggPartial, AnalyzeRecorder, Data, EmptyResultExec, ExecPlan,
    GroupCardinalityError, LazyKeys, QueryError,
    InProcessPlanDispatcher, LeafExecPlan, NonLeafExecPlan, PlanDispatcher,
    QueryResultLike, RawBlock, ScalarResult, _FUSED_CACHE_LOCK,
    _FUSED_GROUP_CACHE, _FUSED_MINMAX_PAD_CACHE, _FUSED_PLAN_CACHE,
    _FUSED_VALS_CACHE,
    _align_hist_schemes, _block_empty, _fused_vals_budget,
    _group_cache_insert, _group_cache_lookup, _lru_touch,
    _note_mirror_limit, _union_scheme, _vals_nbytes,
    present_partial, reduce_partials)
from filodb_tpu.query.transformers import (  # noqa: F401
    AbsentFunctionMapper, AggregateMapReduce, AggregatePresenter,
    InstantVectorFunctionMapper, LimitFunctionMapper,
    MiscellaneousFunctionMapper, PeriodicSamplesMapper,
    RangeVectorTransformer, RepeatToGridMapper, ScalarFunctionMapper,
    ScalarOperationMapper, SortFunctionMapper, VectorFunctionMapper,
    _CANDIDATE_OPS, _dollar_to_backslash, _group_ids)
from filodb_tpu.query.leafexec import (  # noqa: F401
    MultiSchemaPartitionsExec, SelectPersistedSegmentsExec,
    ScalarBinaryOperationExec,
    ScalarFixedDoubleExec, TimeScalarGeneratorExec, _estimate_scan)
from filodb_tpu.query.nonleaf import (  # noqa: F401
    BinaryJoinExec, DistConcatExec, LocalPartitionDistConcatExec,
    ReduceAggregateExec, RemoteAggregateExec, SetOperatorExec,
    StitchRvsExec, SubqueryExec)
from filodb_tpu.query.metaexec import (  # noqa: F401
    LabelValuesExec, MetadataMergeExec, PartKeysExec, SelectChunkInfosExec,
    _canon)
from filodb_tpu.query.rangevector import (  # noqa: F401 — the original
    # module re-exported these transitively; keep import-path compat
    QueryContext, QueryResult, QueryStats, RangeVectorKey, ResultBlock,
    concat_blocks, remove_nan_series)
from filodb_tpu.core.index import ColumnFilter, Equals  # noqa: F401
from filodb_tpu.ops.timewindow import (  # noqa: F401
    PAD_TS, make_window_ends, to_offsets)
