"""Tiny sizes, for the benchmark's CPU tests, of the configurations that
``tests/conftest.py``'s ``TINY`` does not list: they are added to it as
that module is registered, so that its tiny checkout covers every
configuration ``BENCHMARK.json`` names."""

from pathlib import Path

TESTS_CONFTEST = Path(__file__).resolve().parent / "tests" / "conftest.py"

MORE_TINY = {
    "granite-4.0-h-micro-period": {
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
        "mamba_chunk_size": 32, "shared_intermediate_size": 128,
        "inputs": [[128, 64]]},
}


def pytest_plugin_registered(plugin):
    if getattr(plugin, "__file__", None) and \
            Path(plugin.__file__).resolve() == TESTS_CONFTEST:
        for name, sizes in MORE_TINY.items():
            plugin.TINY.setdefault(name, sizes)
