"""Pure-jnp oracle for the min-plus DP transition.

The canonical implementation lives in repro.core.dp (the DP uses it
directly when the kernel is disabled); re-exported here so kernel tests
follow the standard kernels/<name>/{ref,ops} layout.
"""

from repro.core.dp import minplus_step_jnp as minplus_step_ref  # noqa: F401
