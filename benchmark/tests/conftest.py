def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: starts benchmark/run.py --rehearse-tiny as a child "
                   "process on the CPU (minutes)")
