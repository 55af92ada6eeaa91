"""What the tests read out of a traced program."""


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and its sub-jaxprs, but not the inside of
    a ``pallas_call`` (a kernel's own body)."""
    import jax
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.tree.leaves(
                list(eqn.params.values()),
                is_leaf=lambda x: isinstance(x, (Jaxpr, ClosedJaxpr))):
            if isinstance(sub, ClosedJaxpr):
                sub = sub.jaxpr
            if isinstance(sub, Jaxpr):
                yield from _equations(sub)


def pallas_call_names(jaxpr):
    """The names of the ``pallas_call`` equations in ``jaxpr``, sub-jaxprs
    included."""
    return [eqn.params["name"] for eqn in _equations(jaxpr)
            if eqn.primitive.name == "pallas_call"]


def primitives_outside_kernels(jaxpr):
    """The names of the primitives ``jaxpr`` applies outside its Pallas
    kernels, sub-jaxprs included."""
    return {eqn.primitive.name for eqn in _equations(jaxpr)}
