"""Check kind ``logits_window``: a language model served in token sessions
that are fed in several many-token TURNS and then stepped, most of whose
layers read a WINDOW of the latest positions from a ring while a few read
every position (``models/smallthinker.py``).

The comparison is ``checks/logits_state.py``'s, number for number: every
request's answer, each turn's last position and each step, as the served
path gave them through the two geometries of rows and merged step
launches, against the reference's full causal pass with the window as a
MASK; medians over the seed's sensitivity in two classes, the answers
that moved, the near ties and the worst answer. What the two classes
mean here: ``check.context_split`` is the WINDOW, so a SHORT answer (a
context of at most the window) reads the same keys in a window layer as
in a full one, and a LONG answer is one whose window layers no longer see
the stream's first positions, and whose ring has wrapped once the stream
is longer than it. A served path that ignores the window passes the
short class and fails the long one; one that rotates the full layers'
queries and keys fails both (``check_window.py``).

The harness's five functions; all but ``launch_request`` are
``checks/logits_state.py``'s.
"""

from __future__ import annotations

from benchmarks.checks import logits
from benchmarks.checks.logits_state import answered, differences, entry, expected, perturbed, served, well_formed  # noqa: F401


def launch_request(request: dict, b) -> dict:
    """``checks/logits.py``'s. A program without this family (the parent
    of the PR that brought it) fails HERE, at once, in the first seconds
    of set-up: before 11 GB of weights are drawn and the reference has
    run over 27k tokens for a server that cannot load the entry."""
    from triton_client_tpu.models import smallthinker  # noqa: F401

    return logits.launch_request(request, b)
