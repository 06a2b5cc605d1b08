"""glt_tpu — a TPU-native graph-learning framework.

A from-scratch JAX/XLA re-design with the capabilities of
GraphLearn-for-PyTorch (graph sampling, unified feature store, distributed
sampling/training), built for TPU: static shapes, SPMD meshes and XLA
collectives; every hot path is a compiled XLA program.
"""

__version__ = '0.1.0'

from . import typing  # noqa: F401
from . import utils  # noqa: F401
from . import obs  # noqa: F401
from . import data  # noqa: F401
from . import ops  # noqa: F401
from . import sampler  # noqa: F401
from . import loader  # noqa: F401
from . import models  # noqa: F401
from . import channel  # noqa: F401
from . import partition  # noqa: F401
from . import parallel  # noqa: F401
from . import distributed  # noqa: F401
from . import resilience  # noqa: F401
from . import serving  # noqa: F401
from . import stream  # noqa: F401
