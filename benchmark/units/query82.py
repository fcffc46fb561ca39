"""Plain reference of TPC-DS query 82 (query82.tpl beside this file): the
stocked and store-sold items of ``_inventory.stocked_items``."""
from benchmark.units._inventory import stocked_items


def reference(wh, params):
    return stocked_items(wh, params, "store_sales", "ss_item_sk")
