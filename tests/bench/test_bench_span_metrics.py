"""The readers of the save and restore split: per save or per resume of
the window's timers, self times by subtraction of the timers nested in
them, and nothing where the program has no such timer."""
import pytest

from bench import harness

SAVE = ["shadow_d2h_s", "shadow_copy_s", "ckpt_submit_s"]
RESTORE = ["restore_io_s", "restore_decode_s", "restore_assemble_s", "restore_place_s"]


def _ctx(kind: str, timings: dict) -> dict:
    return {"kind": kind, "stalls_s": [1.0, 1.0], "resumes": 2, "timings": timings}


SAVE_T = {"shadow/fetch": (5.0, 88), "shadow/d2h": (3.0, 88), "shadow/copy": (1.8, 88),
          "ckpt/submit": (0.4, 2)}
RESTORE_T = {"restore/eager": (20.0, 2), "restore/leaf": (19.8, 86),
             "restore/assemble": (18.0, 86), "store/read": (2.0, 9476),
             "store/decode": (6.0, 9476)}


@pytest.mark.parametrize("name,kind,timings,want", [
    ("shadow_d2h_s", "train", SAVE_T, 1.5),
    ("shadow_copy_s", "train", SAVE_T, 0.9),
    ("ckpt_submit_s", "train", SAVE_T, 0.2),
    ("restore_io_s", "resume", RESTORE_T, 1.0),
    ("restore_decode_s", "resume", RESTORE_T, 3.0),
    ("restore_assemble_s", "resume", RESTORE_T, 5.0),   # 18 less 2 and 6
    ("restore_place_s", "resume", RESTORE_T, 0.9),      # 19.8 less 18
    # every chunk served from the store's cache: no read, no decode
    ("restore_assemble_s", "resume", {"restore/leaf": (3.0, 86),
                                      "restore/assemble": (2.0, 86)}, 1.0),
])
def test_reader_splits_the_window_timers(name, kind, timings, want):
    assert harness.load_reader(name)(_ctx(kind, timings)) == pytest.approx(want)


@pytest.mark.parametrize("name", SAVE + RESTORE)
def test_reader_reads_nothing_without_the_timers(name):
    """A program without these timers (the parent of the split) leaves the
    metric out of the line."""
    old = {"shadow/fetch": (5.0, 88), "restore/eager": (20.0, 2)}
    kind = "train" if name in SAVE else "resume"
    assert harness.load_reader(name)(_ctx(kind, old)) is None


@pytest.mark.parametrize("name", RESTORE)
def test_restore_readers_read_nothing_in_a_save_cell(name):
    assert harness.load_reader(name)(_ctx("train", RESTORE_T)) is None
