"""The program's own record of each training epoch, for the readers of
the phase metrics.

``Trainer.train_epoch()`` leaves one ``train_epoch`` record an epoch in
its span ring (``trainer.spans.spans``) and in the run's JSONL: the
seconds of each phase, the time to the first dispatch, the steps
(docs/OBSERVABILITY.md). A reader is handed ``run`` and nothing else,
and the driver that builds ``run`` does not pass the records on (it is
the accepted yardstick and no PR but a ``benchmark`` one may edit it),
so ``window_epochs`` takes them from ``run["epoch_records"]`` where a
driver put them there and else from the Trainer that is alive in this
process. A program from before the records has none: every reader then
finds nothing to read.
"""

from __future__ import annotations

import gc
from typing import Any, Dict, List, Optional


def live_trainer():
    """The ``Trainer`` of this process, the one that trained last where
    several are alive (a test session); None where there is none."""
    from p2p_tpu.train.loop import Trainer

    def last_epoch_ts(trainer) -> float:
        return max((s["ts"] for s in trainer.spans.spans
                    if s["name"] == "train_epoch"), default=0.0)

    # type(o), not isinstance: a dead weak proxy raises on the latter
    trainers = [o for o in gc.get_objects()
                if issubclass(type(o), Trainer) and hasattr(o, "spans")]
    return max(trainers, key=last_epoch_ts, default=None)


def window_epochs(run: Dict[str, Any]) -> Optional[List[dict]]:
    """The records of the epochs the measured window ran: the newest ones
    whose steps add up to the window's (``run["steps"]``). None where the
    program keeps no such records or they do not add up."""
    steps = run.get("steps")
    if not steps:
        return None
    records = run.get("epoch_records")
    if records is None:
        trainer = live_trainer()
        records = [] if trainer is None else [
            s for s in trainer.spans.spans if s["name"] == "train_epoch"]
    window: List[dict] = []
    for record in reversed(records):
        if steps <= 0:
            break
        window.append(record)
        steps -= record.get("steps", 0)
    return window[::-1] if window and steps == 0 else None
