"""resnet50 at test size: the published block, stem 8, widths 4-32, blocks
2-1-2-1, a 32x32x3 input and 10 classes."""

import importlib.util
import os

_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..",
                     "configs", "resnet50.py")
_spec = importlib.util.spec_from_file_location("resnet50_published", _path)
_published = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_published)
forward = _published.forward


def param_shapes(input_shape):
    return _published.param_shapes(input_shape, stem=8, widths=(4, 8, 16, 32),
                                     blocks=(2, 1, 2, 1), classes=10)
