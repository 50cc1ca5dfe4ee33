"""Background heal: percent of the window's MRF pushes that the queue set aside or absorbed - pushes that could write nothing, each shard wanting on a drive that is gone - over all its pushes (kernel-stats.heal.parked {set_aside, absorbed} / heal.pushed, window deltas). None on a program without ``heal.parked``, and where no push moved."""
import ledger_readers


def read(run):
    both = ledger_readers._tables(run, "heal", dict)
    if both is None or not all(isinstance(h.get("parked"), dict) for h in both):
        return None
    a, b = both
    pushes = sum(b["pushed"].values()) - sum(a["pushed"].values())
    parked = sum(b["parked"].get(e, 0) - a["parked"].get(e, 0) for e in ("set_aside", "absorbed"))
    return ledger_readers._per(parked, pushes, 100.0)
