"""What the expert layer's test files share (a plain module; pytest collects
nothing here)."""

from jax.extend import core as jex_core


def sub_jaxprs(value):
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from sub_jaxprs(item)


def all_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for sub in sub_jaxprs(value):
                yield from all_jaxprs(sub)
