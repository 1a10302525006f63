"""Models: twins of every model the reference constructs, plus BASELINE's.

- :class:`LinearRegressor` — ``nn.Linear(20, 1)`` (reference ``ddp_gpus.py:81``)
- :class:`SampleModel` — ``Linear(32, 2)`` with observable per-device batch
  split (reference ``01.data_parallel.ipynb`` cell 9)
- :class:`MLP` — generic 2-layer MLP (BASELINE config "02.ddp_toy_example")
- :class:`ToyModel` — the 2-stage ``Linear(10000,10) -> ReLU -> Linear(10,5)``
  model-parallel toy (reference ``03.model_parallel.ipynb`` cell 7)
- :func:`resnet18` / :func:`resnet50` — torchvision-architecture ResNets
  (reference ``03.model_parallel.ipynb`` cells 15/18; BASELINE ResNet-18)
- :func:`model_size` — parameter-count util (reference cell 20)
"""

from pytorch_distributed_training_tutorials_tpu.models.mlp import (  # noqa: F401
    LinearRegressor,
    SampleModel,
    MLP,
    ToyModel,
)
from pytorch_distributed_training_tutorials_tpu.models.resnet import (  # noqa: F401
    ResNet,
    resnet18,
    resnet34,
    resnet50,
)
from pytorch_distributed_training_tutorials_tpu.models.transformer import (  # noqa: F401
    TransformerConfig,
    TransformerLM,
    TP_RULES,
    ep_rules,
)
from pytorch_distributed_training_tutorials_tpu.models.moe import (  # noqa: F401
    MoEFFN,
    MOE_RULES,
    moe_aux_loss,
)
from pytorch_distributed_training_tutorials_tpu.models.utils import (  # noqa: F401
    model_size,
)
from pytorch_distributed_training_tutorials_tpu.models.generate import (  # noqa: F401
    generate,
)
from pytorch_distributed_training_tutorials_tpu.models.sampling import (  # noqa: F401
    filter_logits,
    sample_logits,
    sample_logits_per_slot,
)
from pytorch_distributed_training_tutorials_tpu.models.transformer import (  # noqa: F401
    load_quantized_lm,
    quantize_lm_params,
    stack_quantized_lm_params,
)
