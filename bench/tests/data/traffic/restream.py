"""A throwaway traffic kind for the tests, found by its file name: the
stream kind under another name."""

import harness

_stream = harness.load_module(harness.BENCH / "traffic" / "stream.py")
SPANS = _stream.SPANS


class Run(_stream.Run):
    pass
