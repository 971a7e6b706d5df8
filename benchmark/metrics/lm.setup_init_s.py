import os

from lib import lm_scopes, manifest, program_spans

__doc__ = """The ``provider.init_params`` span before the window (the parameters drawn layer by layer on the device, bfloat16), as ``setup.init_s`` reads it, matched through this path's clock."""


def read(run):
    if lm_scopes.aligned(run) is None:
        return None
    return manifest.load_module(os.path.join(os.path.dirname(__file__), "setup.init_s.py")).read(run)
