-- define [MANUFACT] = uniform_int(1, 1000)
-- define [MONTH] = uniform_int(11, 12)
SELECT dt.d_year, item.i_brand_id AS brand_id, item.i_brand AS brand,
       SUM(ss_ext_sales_price) AS sum_agg
FROM date_dim dt, store_sales, item
WHERE dt.d_date_sk = store_sales.ss_sold_date_sk
  AND store_sales.ss_item_sk = item.i_item_sk
  AND item.i_manufact_id = [MANUFACT]
  AND dt.d_moy = [MONTH]
GROUP BY dt.d_year, item.i_brand, item.i_brand_id
ORDER BY dt.d_year, sum_agg DESC, brand_id
LIMIT 100
