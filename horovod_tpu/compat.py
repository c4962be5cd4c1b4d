"""The one query this package makes of jax's trace context.

The package is written against the installed jax API and calls it
directly (``jax.shard_map`` with ``check_vma``, ``jax.typeof``,
``jax.lax.pcast``, ``jax.lax.axis_size``). What stays here is the lookup
of the named axes bound in the current trace, which several modules need
and which reads one ``jax.sharding`` entry point.
"""

import jax


def bound_axis_names():
    """Mesh axis names bound in the current trace (inside ``shard_map`` /
    any named-axis context); ``()`` at top level."""
    abstract_mesh = jax.sharding.get_abstract_mesh()
    if abstract_mesh.empty:
        return ()
    return tuple(abstract_mesh.axis_names)
