from .ops import minplus_step  # noqa: F401
