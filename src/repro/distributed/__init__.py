from repro.distributed.collectives import microbatch_grads  # noqa: F401
from repro.distributed.compression import (  # noqa: F401
    compress_with_feedback,
    compressed_psum,
    dequantize_int8,
    init_error_feedback,
    quantize_int8,
)
from repro.distributed.fault_tolerance import (  # noqa: F401
    FailureInjector,
    StepFailure,
    StragglerDetector,
    run_with_retries,
)
from repro.distributed.sharding import (  # noqa: F401
    data_axes,
    all_axes,
    gnn_batch_shardings,
    gnn_spec_fn,
    lm_batch_shardings,
    mf_batch_shardings,
    mf_spec_fn,
    recsys_batch_shardings,
    recsys_spec_fn,
    transformer_param_shardings,
    transformer_spec,
    tree_shardings,
    decode_state_spec_fn,
)
