"""``tools/serve_schedule_replay.py``: the host replay of a serving cell's
schedule at a constant step time, pinned to the readings ``PERF.md`` section 7
tabulates (issue 28: below the knee ``serve_out_tokens_per_s`` refuses a
shorter step in the chat cell) and to the ones issue 39's prediction rests on
(the hybrid cell reads MORE at every shorter step; the document cell is
blocked below ~24 ms). Arithmetic on ``benchmarks/lib/traffic.py`` and the
cells' workload files: a change to either moves these numbers, and then the
table is stale too.
"""

import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location("serve_schedule_replay", ROOT / "tools" / "serve_schedule_replay.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# cell, step ms, delivered tokens/s, owed at the open, owed at the close: PERF.md section 7's table
TABLE = [
    ("mistral7b.serve_chat", 88, 104.06, 926, 1104),
    ("mistral7b.serve_chat", 25, 108.58, 339, 290),
    ("mistral7b.serve_chat", 15, 105.12, 129, 253),
    ("mistral7b.serve_chat", 10.9, 103.90, 41, 226),
    ("ouro2.6b.serve_reason", 102, 96.72, 759, 701),
    ("ouro2.6b.serve_reason", 55, 100.78, 451, 192),
    ("ouro2.6b.serve_reason", 30, 97.36, 164, 77),
    ("nemotron3nano.serve_chat", 33.5, 522.66, 1544, 2899),  # the ledger reads 522.79 at a 33.3 ms step (PR 38)
]


@pytest.mark.parametrize("cell,step_ms,tokens_per_s,at_open,at_close", TABLE)
def test_the_replay_gives_the_tabulated_reading(tool, cell, step_ms, tokens_per_s, at_open, at_close):
    got = tool.replay(tool.load_cell(cell), step_ms / 1e3)
    assert round(got["tokens_per_s"], 2) == tokens_per_s
    assert (got["owed_at_open"], got["owed_at_close"]) == (at_open, at_close)
    assert 50.0 <= got["closed_at_s"] < 50.0 + step_ms / 1e3  # the first instant at or after the window's end


def test_a_shorter_step_reads_more_in_the_hybrid_cell_and_less_in_the_other_two(tool):
    """What decides which serving items a ``perf_opt`` issue may take while
    ``serve_out_tokens_per_s`` guards the cells below the knee at 1 %."""
    hybrid = [tool.replay(tool.load_cell("nemotron3nano.serve_chat"), ms / 1e3) for ms in (33.5, 31, 29, 27, 25, 22)]
    rates = [round(r["tokens_per_s"], 2) for r in hybrid]
    assert rates == [522.66, 524.18, 525.30, 526.94, 528.64, 531.18] and rates == sorted(rates)
    assert [r["owed_at_close"] for r in hybrid] == [2899, 2732, 2591, 2416, 2241, 1966]
    assert hybrid[0]["ttft_p95_ms"] == pytest.approx(83.9 * 33.5, rel=2e-3)  # the 1326-token prompt's 83 chunks
    doc = [round(tool.replay(tool.load_cell("deepseekv2.serve_doc"), ms / 1e3)["tokens_per_s"], 2)
           for ms in (32.2, 30, 25, 20)]
    assert doc == [117.18, 118.24, 117.02, 115.60]
    assert doc[3] < doc[0] * 0.99 < doc[2]  # under ~24 ms the document cell loses more than its 1 % bound
    chat = [round(tool.replay(tool.load_cell("mistral7b.serve_chat"), ms / 1e3)["tokens_per_s"], 2) for ms in (25, 5)]
    assert chat == [108.58, 105.40] and chat[1] < chat[0] * 0.99


def test_the_tool_prints_a_row_a_step_time(tool, capsys, monkeypatch):
    monkeypatch.setattr("sys.argv", ["serve_schedule_replay.py", "--workload", "nemotron3nano.serve_chat",
                                     "--step-ms", "33.5,25"])
    assert tool.main() == 0
    out = capsys.readouterr().out.splitlines()
    assert "not a run" in out[0] and out[2].split() == ["33.5", "522.66", "1544", "2899", "2810"]
    assert out[3].split()[:2] == ["25", "528.64"]
