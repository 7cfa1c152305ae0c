from repro.optim.optimizers import (  # noqa: F401
    Optimizer,
    adagrad,
    adamw,
    sgd,
    clip_by_global_norm,
    cosine_schedule,
    zero1_specs,
)
from repro.optim.remap import (  # noqa: F401
    remap_opt_state,
    zeros_like_moments,
)
from repro.optim.compression import (  # noqa: F401
    int8_compress,
    int8_decompress,
    compressed_grad_transform,
)
