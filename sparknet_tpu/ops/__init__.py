from .registry import LayerImpl, register_layer, get_layer_impl, registered_types
from . import data, vision, neuron, common, loss, python_layer, sequence  # noqa: F401  (register ops)
from .python_layer import register_python_layer  # noqa: F401
