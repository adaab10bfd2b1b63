"""mxnet_tpu — a TPU-native deep learning framework with the capability
surface of pre-1.0 Apache MXNet (reference: shujonnaha/incubator-mxnet).

See SURVEY.md at the repo root for the reference structural analysis and
README.md for the architecture of this re-design:  imperative NDArray ops
dispatch to cached XLA executables, bound Symbol graphs compile to a single
XLA computation, distribution is jax.sharding meshes + XLA collectives over
ICI/DCN, and Gluon-style blocks hybridize into jitted programs.
"""
from . import base
from . import compile_cache
from . import attribute
from .attribute import AttrScope
from .base import MXNetError, TrainingPreempted, RecompileStorm
from . import context
from .context import Context, cpu, gpu, tpu, current_context
from . import random
from . import ops
from . import operator
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import autograd
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from .executor import Executor
from . import initializer
from . import initializer as init
from . import optimizer
from . import lr_scheduler
from . import metric
from . import io
from . import recordio
from . import image
from . import kvstore
from . import kvstore as kv
from . import callback
from . import profiler
from . import rtc
from . import visualization
from . import visualization as viz
from . import predictor
from .predictor import Predictor
from . import monitor
from .monitor import Monitor
from . import model
from . import module
from . import module as mod
from .module import Module, BucketingModule
from . import rnn
from . import parallel
from . import test_utils
from .model import save_checkpoint, load_checkpoint
from . import checkpoint
from .checkpoint import CheckpointManager, CheckpointState
from . import testing
from . import models
from . import serve
from . import name
from . import libinfo
from . import executor_manager
from . import kvstore_server
from . import contrib

__version__ = "0.1.0"
