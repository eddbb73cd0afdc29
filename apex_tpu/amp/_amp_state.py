"""Process-global amp bookkeeping.

Analog of the reference's ``apex/amp/_amp_state.py`` (SURVEY.md §5
metrics/observability row): holds the verbosity knob consulted by
``maybe_print`` and the overflow log line. In the rebuild almost all state
is carried functionally; only human-facing verbosity lives here.
"""

from __future__ import annotations


class AmpState:
    def __init__(self):
        self.verbosity = 1
        self.allow_incoming_model_not_fp32 = False
        # last handle returned by amp.initialize — backs the module-level
        # amp.scale_loss/state_dict conveniences (reference keeps the same
        # process-global handle in its _amp_state)
        self.handle = None
        # None = auto: in-graph overflow logging uses jax.debug.print, a
        # host callback inside the step — on an accelerator that is a
        # device-to-host round trip per step, so auto enables it only on
        # the CPU backend; set explicitly via set_ingraph_logging().
        self.ingraph_logging = None

    def maybe_print(self, msg: str, rank0: bool = False):
        # stdout, like the reference's plain print() — downstream scripts
        # grep training stdout for the overflow line
        if self.verbosity >= 1:
            print(msg)


_amp_state = AmpState()


def get_verbosity() -> int:
    return _amp_state.verbosity


def set_verbosity(v: int):
    _amp_state.verbosity = v


def maybe_print(msg: str):
    _amp_state.maybe_print(msg)


def set_ingraph_logging(enabled):
    """Force in-graph (jax.debug.print) overflow logging on or off.

    Pass None to restore the default (on only on the CPU backend,
    where the callback costs no device round trip)."""
    _amp_state.ingraph_logging = enabled


def ingraph_logging_enabled() -> bool:
    if _amp_state.ingraph_logging is not None:
        return _amp_state.ingraph_logging
    import jax

    return jax.default_backend() == "cpu"
