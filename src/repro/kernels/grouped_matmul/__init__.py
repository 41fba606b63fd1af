from repro.kernels.grouped_matmul.ops import (  # noqa: F401
    expert_gmm, grouped_matmul)
