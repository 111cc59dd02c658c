# -*- coding: utf-8 -*-
"""
distributed_dot_product_tpu — a TPU-native (JAX/XLA/shard_map) framework for
operator-level sequence (context) parallelism of dot-product attention.

Brand-new implementation with the capabilities of the reference library
``andfoy/py-distributed-dot-product`` (PyTorch + Horovod/NCCL/MPI): the three
distributed sequence matmuls ``A·Bᵀ`` ("nt"), ``A·B`` ("all") and ``Aᵀ·B``
("tn") with a chunk-size (``offset``) memory/time knob, their custom
gradients, and a multi-head ``DistributedDotProductAttn`` module that shards
the time axis ``T`` across ``N`` devices so each holds a ``(*, T/N, d)``
slice (reference README.md:4-15).

Architecture (TPU-first, not a port):

- one compiled SPMD program over a 1-D ``jax.sharding.Mesh`` axis ``'seq'``
  replaces the reference's N OS processes + eager named collectives
  (reference comm.py:6-10, functions.py:95);
- ``lax.all_gather`` / ``lax.psum_scatter`` / ``lax.ppermute`` over ICI
  replace Horovod allgather/allreduce over NCCL/MPI (reference
  functions.py:95,143-147);
- ``jax.custom_vjp`` replaces ``torch.autograd.Function`` (reference ops.py);
- single-process multi-device CPU simulation
  (``--xla_force_host_platform_device_count``) replaces
  ``horovodrun -np N --mpi pytest`` (reference README.md:171-177).

Version parity note: the reference exposes ``VERSION_INFO`` in its
``__init__.py`` (reference __init__.py:9-10); we keep the same convention.
"""

from distributed_dot_product_tpu._version import (  # noqa: F401
    VERSION_INFO, __version__,
)

from distributed_dot_product_tpu.utils.comm import (  # noqa: F401
    SEQ_AXIS, get_rank, get_world_size, is_main_process, synchronize, init,
)
from distributed_dot_product_tpu.parallel.mesh import (  # noqa: F401
    seq_mesh, seq_spec, replicated_spec, shard_seq,
)
from distributed_dot_product_tpu.ops.functions import (  # noqa: F401
    distributed_matmul_nt, distributed_matmul_tn, distributed_matmul_all,
)
from distributed_dot_product_tpu.ops.ops import (  # noqa: F401
    matmul_nt, matmul_all, matmul_tn,
    RightTransposeMultiplication, FullMultiplication,
    LeftTransposeMultiplication,
)
from distributed_dot_product_tpu.models.attention import (  # noqa: F401
    DistributedDotProductAttn, apply_seq_parallel, decode_seq_parallel,
    make_decode_step,
)
from distributed_dot_product_tpu.models.ring_attention import (  # noqa: F401
    local_attention_reference, ring_attention,
)
from distributed_dot_product_tpu.models.decode import (  # noqa: F401
    DecodeCache, append_kv, append_kv_sharded, append_kv_slots,
    decode_attention, decode_impl_traces, decode_kernel_eligible,
    decode_step, init_cache, init_slot_cache, reset_slot,
    slots_all_finite,
)
from distributed_dot_product_tpu.models.dense import (  # noqa: F401
    OwnedDense, quantize_dense_params, quantize_kernel,
)
from distributed_dot_product_tpu.models.transformer import (  # noqa: F401
    TransformerBlock, TransformerStack,
)
from distributed_dot_product_tpu.models.lm import (  # noqa: F401
    TransformerLM, greedy_generate, lm_targets,
)
from distributed_dot_product_tpu.models.ulysses_attention import (  # noqa: F401
    ulysses_attention,
)
from distributed_dot_product_tpu.ops.pallas_attention import (  # noqa: F401
    flash_attention,
)
from distributed_dot_product_tpu.ops.rope import (  # noqa: F401
    rope, rope_seq_parallel,
)
from distributed_dot_product_tpu.utils.checkpoint import (  # noqa: F401
    CheckpointMismatchError, TrainState, gc_old_steps, latest_step,
    recover_interrupted, restore, save, wait,
)
from distributed_dot_product_tpu.train_loop import (  # noqa: F401
    TrainLoopConfig, TrainLoopResult, run_training,
)
from distributed_dot_product_tpu.serve import (  # noqa: F401
    HealthMonitor, KernelEngine, Readiness, RejectReason, RejectedError,
    Scheduler, ServeConfig,
)
