import tiny


def pytest_configure(config):
    tiny.cpu_cache()
