def pytest_configure(config):
    config.addinivalue_line("markers", "slow: multi-device subprocess tests")
    config.addinivalue_line(
        "markers",
        "byz: Byzantine-robust aggregation + fault-injection coverage "
        "(selected as its own CI step so robustness regressions are visible)",
    )
    config.addinivalue_line(
        "markers",
        "fed: federated scenario tier (client sampling, residual-pool "
        "persistence, weighted server combine) — selected as its own CI step "
        "so fed regressions are visible",
    )
    config.addinivalue_line(
        "markers",
        "pallas: exercises a Pallas kernel in interpret mode, its reference "
        "oracle, or its compile for a described TPU; runs everywhere and is "
        "selected as its own CI step so kernel regressions are visible",
    )

