"""Device ms a traced step of the leaf operations under the program's
``ffn`` scope (``repro.obs.scopes``; ``scopes.py`` maps them)."""

import scopes


def read(run):
    return scopes.scope_ms(run, "ffn")
