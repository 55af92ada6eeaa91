"""What the tests read out of a traced program."""


def pallas_call_names(jaxpr):
    """The names of the ``pallas_call`` equations in ``jaxpr``, sub-jaxprs
    included."""
    import jax
    from jax.extend.core import ClosedJaxpr, Jaxpr
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.tree.leaves(
                list(eqn.params.values()),
                is_leaf=lambda x: isinstance(x, (Jaxpr, ClosedJaxpr))):
            if isinstance(sub, ClosedJaxpr):
                sub = sub.jaxpr
            if isinstance(sub, Jaxpr):
                names += pallas_call_names(sub)
    return names
