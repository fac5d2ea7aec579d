"""Model zoo for examples/benchmarks, mirroring the reference's example/
directory (PyTorch MNIST, synthetic ResNet-50, GluonNLP BERT-large —
SURVEY.md §6 configs)."""

from .evabyte import (  # noqa: F401
    EvaByte,
    EvaByteConfig,
    evabyte_loss,
    evabyte_tiny,
)
from .glm_lite import (  # noqa: F401
    GlmLite,
    GlmLiteConfig,
    glm_lite_loss,
    glm_lite_tiny,
)
from .llama import (  # noqa: F401
    Llama,
    LlamaConfig,
    llama3_8b,
    llama_tiny,
)
from .ling import (  # noqa: F401
    Ling,
    LingConfig,
    ling_loss,
    ling_tiny,
)
from .mellum import (  # noqa: F401
    Mellum,
    MellumConfig,
    mellum_loss,
    mellum_tiny,
)
from .mlp import MLP, mnist_mlp  # noqa: F401
from .nemotron_h import (  # noqa: F401
    NemotronH,
    NemotronHConfig,
    nemotron_h_tiny,
    nemotron_loss,
)
from .olmoe import (  # noqa: F401
    Olmoe,
    OlmoeConfig,
    olmoe_loss,
    olmoe_tiny,
)
from .qwen3_next import (  # noqa: F401
    Qwen3Next,
    Qwen3NextConfig,
    qwen3_next_loss,
    qwen3_next_tiny,
)
from .zaya import (  # noqa: F401
    Zaya,
    ZayaConfig,
    zaya_loss,
    zaya_tiny,
)
from .resnet import (  # noqa: F401
    ResNet,
    VGG,
    resnet18,
    resnet50,
    resnet_tiny,
    vgg16,
)
