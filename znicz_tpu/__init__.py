"""znicz_tpu — a TPU-native deep-learning framework.

A from-scratch rebuild of the capabilities of cnxtech/veles.znicz
(Samsung VELES core framework + the Znicz neural-network plugin),
designed TPU-first:

- the user-facing model is the reference's: a ``Workflow`` graph of
  ``Unit`` objects wired by control links (``link_from``) and data links
  (``link_attrs``), with boolean gates, a ``Repeater`` training loop,
  paired forward/gradient units, loaders, decision/early-stopping,
  snapshot/resume, plotting and hyperparameter tuning
  (reference: veles/units.py :: Unit, veles/workflow.py :: Workflow);
- the execution model is idiomatic JAX/XLA: the accelerated segment of
  the graph (forwards -> evaluator -> gradient units) is traced once into
  a single pure step function, jitted, and ``shard_map``-ped over a
  ``jax.sharding.Mesh`` with ``lax.psum`` gradient reduction over ICI —
  replacing the reference's per-unit OpenCL/CUDA kernel enqueues and its
  ZeroMQ master-slave parameter server
  (reference: veles/accelerated_units.py :: AcceleratedUnit,
  veles/server.py :: Server, veles/client.py :: Client);
- hand-written kernels (fused SGD update, LRN, dropout PRNG, stochastic
  pooling, Kohonen argmin-update) are Pallas TPU kernels, with XLA-native
  lowerings as the always-available fallback
  (reference: veles.znicz ocl/*.cl + cuda/*.cu).

Blueprint: SURVEY.md.  Targets: BASELINE.md.
"""

__version__ = "0.1.0"

from znicz_tpu.core.config import root, Config
from znicz_tpu.core import prng
from znicz_tpu.core.memory import Array
from znicz_tpu.core.mutable import Bool
from znicz_tpu.core.units import Unit, TrivialUnit
from znicz_tpu.core.workflow import Workflow
from znicz_tpu.core.plumbing import Repeater, StartPoint, EndPoint
from znicz_tpu.core.backends import (AutoDevice, Device, NumpyDevice,
                                     TPUDevice, XLADevice)

__all__ = [
    "root", "Config", "prng", "Array", "Bool", "Unit", "TrivialUnit",
    "Workflow", "Repeater", "StartPoint", "EndPoint",
    "Device", "NumpyDevice", "XLADevice", "TPUDevice", "AutoDevice",
]
