"""tpu_compressed_dp — TPU-native compressed-communication data-parallel training.

A brand-new JAX/XLA/Pallas/pjit framework with the capabilities of the AAAI'20
layer-wise compressed-communication reference (see SURVEY.md): six gradient
compression operators at layer-wise or entire-model granularity, simulated and
wire-sparse payloads, error feedback, DAWNBench CIFAR-10 and ImageNet ResNet-50
workloads, phase schedules, checkpointing, and comm observability — all over
`jax.sharding.Mesh` collectives instead of NCCL/Gloo.
"""

__version__ = "0.1.0"

from tpu_compressed_dp.parallel.dp import CompressionConfig  # noqa: F401
from tpu_compressed_dp.parallel.mesh import make_data_mesh, distributed_init  # noqa: F401
from tpu_compressed_dp.train.optim import SGD  # noqa: F401
from tpu_compressed_dp.train.schedules import piecewise_linear  # noqa: F401
from tpu_compressed_dp.train.state import TrainState  # noqa: F401
from tpu_compressed_dp.train.step import make_train_step, make_eval_step  # noqa: F401
