"""Launcher config file of this configuration: the sizes live in the
``.json`` of the same name, which the workflow file reads whole."""
import os

root.bench_decoder.config_json = (            # noqa: F821 (root is injected)
    os.path.splitext(os.path.abspath(__file__))[0] + ".json")
