"""vgg16 at test size: the published structure, widths 8-16 and a 32x32x3 input."""

import importlib.util
import os

_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..",
                     "configs", "vgg16.py")
_spec = importlib.util.spec_from_file_location("vgg16_published", _path)
_published = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_published)
forward = _published.forward


def param_shapes(input_shape):
    return _published.param_shapes(input_shape, widths=(8, 8, 16, 16, 16),
                                     fc=(32, 32), classes=10)
