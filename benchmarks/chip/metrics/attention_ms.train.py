"""Device ms a traced step of the leaf operations under the program's
``attention`` scope (``repro.obs.scopes``; ``scopes.py`` maps them): the
forward, the forward the layer scan's remat recomputes, and the backward."""

import scopes


def read(run):
    return scopes.scope_ms(run, "attention")
