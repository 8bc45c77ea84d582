"""The benchmark's own tests register their one marker here, under the
benchmark's ``paths``, so that a copy of ``BENCHMARK.json``, ``perf/`` and
``tests/perf/`` carries it along (``tests/perf/test_manifest_grows.py``
runs the marked tests over such a copy, grown by a cell)."""


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "manifest_shape: reads BENCHMARK.json and the files it names and starts no run of "
        "perf/run.py; has to pass, as it is, over a manifest that a later PR has appended to",
    )
