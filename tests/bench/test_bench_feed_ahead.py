"""The reader of the train loop's feed counter: the share of steps whose
batch was built ahead, while the device ran the previous step."""
import pytest

from bench import harness


def test_feed_ahead_share_reads_the_loop_counter(monkeypatch):
    from repro.train import trainer

    read = harness.load_reader("train_feed_ahead_share")
    monkeypatch.setattr(trainer, "FEED_TOTALS", {"ahead": 399, "inline": 1})
    assert read({"counters": {}}) == pytest.approx(99.75)
    monkeypatch.setattr(trainer, "FEED_TOTALS", {"ahead": 0, "inline": 0})
    assert read({"counters": {}}) is None


def test_feed_ahead_share_finds_nothing_without_the_counter(monkeypatch):
    """What the parent program has: no counter, no value."""
    from repro.train import trainer

    monkeypatch.delattr(trainer, "FEED_TOTALS")
    assert harness.load_reader("train_feed_ahead_share")({"counters": {}}) is None
