"""The byte count behind ``chunk_step_hbm_roofline.run``, by hand."""
from hbench.costs import chunk_step_bytes


def test_chunk_step_bytes_by_hand():
    # chunk 4: requests in 4 x 13 B (three int32 fields and a bool),
    # outputs 4 x 21 B (five int32 arrays and a bool), 6 table rows of
    # 8 int32 lanes, 4 hotness counters read and written (4 x 8 B).
    assert chunk_step_bytes(4) == 52 + 84 + 192 + 32
    assert chunk_step_bytes(512) == 512 * 34 + 514 * 32 + 512 * 8
