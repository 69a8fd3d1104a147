"""Drivers: ``run(ctx) -> record``.  A cell's traffic file names its
driver; a metric reader takes its number from the record."""
