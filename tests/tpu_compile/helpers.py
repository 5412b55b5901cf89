"""What the families' files read of a compiled program's text and memory."""

GIB = 2**30


def _nbytes(shape):
    import numpy as np

    return int(np.prod(shape.shape)) * shape.dtype.itemsize


def _relaid_out(text, shape):
    """Whether the compiled text holds a ``copy`` that produces ``shape``."""
    import re

    return re.search(re.escape(shape) + r"\{[^}]*\} copy\(", text) is not None


def _kernel_scopes(hlo_text):
    """The ``op_name`` of every Mosaic call in a compiled program's text."""
    import re

    return re.findall(
        r'custom_call_target="tpu_custom_call".*?metadata=\{op_name="([^"]*)"', hlo_text
    )
