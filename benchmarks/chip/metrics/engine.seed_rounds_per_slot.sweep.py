"""Rejection-seeding rounds a seeded slot: the ``seed_rounds`` the window's
launches record on ``dse.harvest`` over their ``seed_slots``.  None where
the program records neither (an older checkout) or the window seeded no
slot."""
from program_spans import window


def read(run):
    snap = window(run)
    if snap is None:
        return None
    slots = rounds = 0
    for s in snap.spans:
        if s.name == "dse.harvest":
            slots += s.attrs.get("seed_slots", 0)
            rounds += s.attrs.get("seed_rounds", 0)
    return rounds / slots if slots else None
