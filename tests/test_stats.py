"""IOTracer (dstat view: per-interval byte buckets) + kind validation."""
import time

import pytest

from repro.core.stats import IOTracer


class TestKindValidation:
    def test_unknown_kind_raises(self):
        tr = IOTracer()
        with pytest.raises(ValueError, match="unknown I/O kind"):
            tr.record("fsync", 10)
        # regression: before the fix, any unknown kind silently counted as a
        # write — totals must stay untouched after the failed record
        t = tr.totals()
        assert t["write_bytes"] == 0 and t["write_ops"] == 0

    @pytest.mark.parametrize("kind", ["read", "write"])
    def test_valid_kinds_accepted(self, kind):
        tr = IOTracer()
        tr.record(kind, 100)
        t = tr.totals()
        assert t[f"{kind}_bytes"] == 100
        assert t[f"{kind}_ops"] == 1


class TestAdapter:
    def test_totals_and_timeline(self):
        tr = IOTracer(interval_s=0.05)
        tr.record("read", 1000)
        tr.record("write", 500)
        time.sleep(0.06)
        tr.record("read", 2000)
        t = tr.totals()
        assert t == dict(read_bytes=3000, write_bytes=500,
                         read_ops=2, write_ops=1)
        rows = tr.timeline()
        assert len(rows) >= 2
        assert rows[0]["read_ops"] == 1 and rows[0]["write_ops"] == 1
        assert sum(r["read_ops"] for r in rows) == 2

    def test_csv_header_and_rows(self):
        tr = IOTracer()
        tr.record("read", 1_000_000)
        csv = tr.to_csv()
        lines = csv.splitlines()
        assert lines[0] == "t_s,read_mb_s,write_mb_s,read_ops,write_ops"
        assert lines[1].startswith("0.0,1.000,")

    def test_reset(self):
        tr = IOTracer()
        for _ in range(100):
            tr.record("read", 10)
        # ops fold into per-interval buckets: no per-op records retained
        assert len(tr._buckets) == 1
        assert tr.totals()["read_ops"] == 100
        tr.reset()
        assert tr.timeline() == []
        assert tr.totals()["read_ops"] == 0
