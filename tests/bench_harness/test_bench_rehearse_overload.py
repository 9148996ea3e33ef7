"""The whole command at toy size on the CPU, serving cell above the
knee: streams dropped at the window's end, tokens a second judged."""

from bench_rehearsal_helper import rehearse


def test_overload_cell_walks_every_path():
    said, would = rehearse("serve-chat-overload", trace=0)
    assert set(would["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert would["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert would["metrics"]["setup_s"]["unit"] == "s"
    assert would["failed"] == 0
    assert said["also"]["setup_s"] > 0
