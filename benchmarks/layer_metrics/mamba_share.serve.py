"""Share of a serving window's device own time in the Mamba mixers of the
timed program (the decode tick), found by the name the program gave them:
instructions that are in, or fuse an operation of, the ``mamba_mixer``
scope (``tpu_dist/models/hybrid.py``: the mixer's four projections, its
convolution, its three inner norms and the ``ssm_step``). The join is on
the tick's own instructions (``trace/scopes.py``), so a prefill's mixers
count under the window's total, not under the share."""

from benchmarks.trace import scopes


def read(obs):
    if "engine_steps" not in obs:
        return None
    return scopes.share(obs, "mamba_mixer")
