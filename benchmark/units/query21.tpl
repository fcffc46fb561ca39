-- define [YEAR] = uniform_int(1998, 2002)
-- define [MONTH] = uniform_int(2, 6)
-- define [DAY] = uniform_int(10, 28)
-- note: TPC-DS draws SALES_DATE between January 31 and July 1 of [YEAR]; the
-- generator's grammar has no date domain, so the date is written from three
-- integer draws inside that range (as query20.tpl does).
SELECT *
FROM (SELECT w_warehouse_name, i_item_id,
             SUM(CASE WHEN d_date < CAST('[YEAR]-0[MONTH]-[DAY]' AS DATE)
                      THEN inv_quantity_on_hand ELSE 0 END) AS inv_before,
             SUM(CASE WHEN d_date >= CAST('[YEAR]-0[MONTH]-[DAY]' AS DATE)
                      THEN inv_quantity_on_hand ELSE 0 END) AS inv_after
      FROM inventory, warehouse, item, date_dim
      WHERE i_current_price BETWEEN 0.99 AND 1.49
        AND i_item_sk = inv_item_sk
        AND inv_warehouse_sk = w_warehouse_sk
        AND inv_date_sk = d_date_sk
        AND d_date BETWEEN (CAST('[YEAR]-0[MONTH]-[DAY]' AS DATE) - INTERVAL 30 DAYS)
                       AND (CAST('[YEAR]-0[MONTH]-[DAY]' AS DATE) + INTERVAL 30 DAYS)
      GROUP BY w_warehouse_name, i_item_id) x
WHERE (CASE WHEN inv_before > 0 THEN inv_after / inv_before ELSE NULL END)
      BETWEEN 2.0 / 3.0 AND 3.0 / 2.0
ORDER BY w_warehouse_name, i_item_id
LIMIT 100
