from . import bert  # noqa: F401
from . import mlp  # noqa: F401
from . import llama  # noqa: F401
from . import glm_moe  # noqa: F401
from . import resnet  # noqa: F401
from . import llama_decode  # noqa: F401
