"""Plain reference of TPC-DS query 37 (query37.tpl beside this file): the
stocked and catalog-sold items of ``_inventory.stocked_items``."""
from benchmark.units._inventory import stocked_items


def reference(wh, params):
    return stocked_items(wh, params, "catalog_sales", "cs_item_sk")
