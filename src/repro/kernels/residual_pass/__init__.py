from .ops import residual_pass
from .ref import residual_pass_ref

__all__ = ["residual_pass", "residual_pass_ref"]
