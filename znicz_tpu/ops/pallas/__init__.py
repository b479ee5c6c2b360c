"""Pallas TPU kernels — the rebuild of the reference's hand-written
.cl/.cu kernel layer (SURVEY.md §3.2 "TPU-native mapping").

Policy: XLA-native lowerings are the default everywhere (XLA already fuses
elementwise chains into matmuls); Pallas versions exist where the
reference's fusion/PRNG semantics are the point — the fused SGD update
(one HBM pass over weights+velocity), dropout with in-kernel counter PRNG,
LRN's sliding-window pair, the implicit-im2col GEMM conv, stochastic
pooling with in-kernel PRNG, and the fused Kohonen
distance+argmin+update step.  Each kernel has an ``interpret=`` switch
so the CPU test mesh can pin it against the jnp oracle
(tests/test_pallas_kernels.py); unit code selects via
``root.common.engine.pallas``.
"""

from znicz_tpu.ops.pallas.sgd import fused_sgd_update  # noqa: F401
from znicz_tpu.ops.pallas.dropout import dropout_forward  # noqa: F401
from znicz_tpu.ops.pallas.lrn import lrn_backward, lrn_forward  # noqa: F401
from znicz_tpu.ops.pallas.conv import conv2d_im2col  # noqa: F401
from znicz_tpu.ops.pallas.conv_bwd import (  # noqa: F401
    conv2d_backward, deconv2d, deconv2d_backward)
from znicz_tpu.ops.pallas.pooling import stochastic_pool  # noqa: F401
from znicz_tpu.ops.pallas.kohonen import som_step  # noqa: F401
from znicz_tpu.ops.pallas.attention import flash_attention  # noqa: F401
from znicz_tpu.ops.pallas.adam import fused_adam_update  # noqa: F401
from znicz_tpu.ops.pallas.gemm import (  # noqa: F401
    fc_backward, fc_forward, matmul)
from znicz_tpu.ops.pallas.grouped import (  # noqa: F401
    gmm, gmm_rows, gmm_rows_t, gmm_weights)
from znicz_tpu.ops.pallas.rope import rope_tail  # noqa: F401
