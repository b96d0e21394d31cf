"""heat_tpu core: the distributed tensor layer
(reference: heat/core/__init__.py)."""

import time as _time

# the start-up record: the clock after each import statement below, in file
# order; ``heat_tpu/__init__.py`` hands them over as children of ``import:core``
_STAGES = []


def _done(what, _now=_time.monotonic, _add=_STAGES.append):
    _add((what, _now()))


from .communication import *
_done("communication")
from .devices import *
_done("devices")
from . import types
_done("types")
from .types import *
_done("types")
from .constants import *
_done("constants")
from .stride_tricks import *
_done("stride_tricks")
from .memory import *
_done("memory")
from . import sanitation
_done("sanitation")
from .sanitation import *
_done("sanitation")
from .dndarray import *
_done("dndarray")
from . import fuse as _fuse_module
_done("fuse")
from .fuse import *
_done("fuse")
from . import autoshard as _autoshard_module
_done("autoshard")
from .autoshard import *
_done("autoshard")
from . import factories
_done("factories")
from .factories import *
_done("factories")
from . import arithmetics
_done("arithmetics")
from .arithmetics import *
_done("arithmetics")
from . import relational
_done("relational")
from .relational import *
_done("relational")
from . import logical
_done("logical")
from .logical import *
_done("logical")
from . import exponential
_done("exponential")
from .exponential import *
_done("exponential")
from . import trigonometrics
_done("trigonometrics")
from .trigonometrics import *
_done("trigonometrics")
from . import rounding
_done("rounding")
from .rounding import *
_done("rounding")
from . import statistics
_done("statistics")
from .statistics import *
_done("statistics")
from . import manipulations
_done("manipulations")
from .manipulations import *
_done("manipulations")
from . import indexing
_done("indexing")
from .indexing import *
_done("indexing")
from . import printing
_done("printing")
from .printing import get_printoptions, set_printoptions
_done("printing")
from . import random
_done("random")
from . import io
_done("io")
from .io import *
_done("io")
from . import checkpoint
_done("checkpoint")
from .checkpoint import *
_done("checkpoint")
from . import tiling
_done("tiling")
from .tiling import *
_done("tiling")
from .base import *
_done("base")
from . import linalg
_done("linalg")
from .linalg import *
_done("linalg")
from ..version import __version__
_done("version")
