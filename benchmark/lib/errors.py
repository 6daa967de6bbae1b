"""The one error of the benchmark's inputs."""


class BenchError(Exception):
    """A fault of the benchmark's inputs (a file, a name, a shape, a
    count); the run prints one line, no result, and exits 2."""
