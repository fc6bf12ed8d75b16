"""What the readers of an EVA cell share: the decode chains' own account of
what they read, from the ``dstpu:serve:dispatch`` spans. Since PR 35 the
engine puts on every dispatch of an EVA model ``attended_rows`` (the sum, over
the call's live rows and steps, of the cache rows attention reads: the closed
windows' summaries and the open window up to the token), ``context_tokens``
(what full attention would read), ``row_steps`` and ``windows_closed``, each
computed on the host from the rows' positions. In a trace of a program without
these args nothing is found and the readers return None."""

from __future__ import annotations

from typing import Dict, List

from benchmarks.lib import spans

EVA_SCOPE = "eva"  # around all of EVA attention in the two serving programs
ARGS = ("attended_rows", "context_tokens", "row_steps", "windows_closed")


def chains(run) -> List[Dict[str, float]]:
    """One entry a decode chain whose ``serve:dispatch`` span lies in the
    window and carries the EVA args."""
    return [{k: float(s.args[k]) for k in ARGS}
            for s in spans.named(spans.of_run(run), "serve:dispatch", kind="chain")
            if all(k in s.args for k in ARGS)]
